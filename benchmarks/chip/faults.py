"""Faults planted in the program under test, to show that the check sees them.

Each is a context manager that swaps one function of the program for a broken
one while a step is built and first traced:

* ``state_unchanged``: the optimizer returns the parameters and its state as
  they came in;
* ``half_batch``: the loss leaves out the second half of every row's
  positions and takes the mean over the rest;
* ``no_exchange``: the reduce-scatter between chips is left out, and each
  chip keeps its own gradient's row block.

Used by ``calibrate.py`` on the chip and by the tests on the CPU; the
benchmark's own runs never plant one.
"""

from __future__ import annotations

import contextlib

import jax


@contextlib.contextmanager
def _swap(module, name: str, fn):
    old = getattr(module, name)
    setattr(module, name, fn)
    try:
        yield
    finally:
        setattr(module, name, old)


@contextlib.contextmanager
def state_unchanged():
    from repro.training import train_step, zero1

    def adamw(grads, state, params, cfg):
        return params, state, {"lr": jax.numpy.zeros(()), "grad_norm": jax.numpy.zeros(())}

    def zero1_update(grads, state, params, *args, **kwargs):
        return adamw(grads, state, params, None)

    with _swap(train_step, "adamw_update", adamw), _swap(zero1, "zero1_update", zero1_update):
        yield


@contextlib.contextmanager
def half_batch():
    from repro.training import train_step

    loss = train_step._loss

    def half(params, cfg, ax, batch):
        labels = batch["labels"]
        cut = labels.at[:, labels.shape[1] // 2:].set(-1)
        return loss(params, cfg, ax, {**batch, "labels": cut})

    with _swap(train_step, "_loss", half):
        yield


@contextlib.contextmanager
def no_exchange():
    from repro.core import collectives

    def local(x, comm, op="add", algorithm="auto", pipeline=None, rows=False):
        flat = x if rows else x.reshape(-1)
        n = flat.shape[0] // comm.size
        return jax.lax.dynamic_slice_in_dim(flat, comm.transport().rank() * n, n)

    with _swap(collectives, "reduce_scatter", local):
        yield


ALL = {"state_unchanged": state_unchanged, "half_batch": half_batch,
       "no_exchange": no_exchange}
