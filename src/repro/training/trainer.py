"""Trainer: the production loop tying every substrate together.

step loop -> data pipeline (prefetched, deterministic) -> train_step (xla or
fmi mode) -> metrics -> async checkpoint every ``ckpt_every`` -> membership
heartbeats -> on failure the :class:`~repro.runtime.ElasticController`
drives the full heal — quiesce (in-flight requests cancelled), regroup
(survivors laid out by :func:`~repro.core.algorithms.build_group`), reshard
(latest committed checkpoint restored onto the rebuilt mesh), resume at the
restored step.  :class:`~repro.runtime.StragglerPolicy` feeds either the
backup-worker plan or the subgroup-reduction mask.

Elastic knobs:

* ``elastic=True`` arms the heal path (requires ``ckpt_dir`` for reshard;
  without a committed checkpoint a heal restarts from initialization).
* ``make_mesh(dp) -> mesh`` rebuilds the device mesh at a new data-parallel
  degree; ``None`` keeps the current mesh (single-host smoke runs).
* ``fault_injector(step) -> [ranks]`` declares ranks failed at a step —
  the deterministic stand-in for real heartbeat loss used by the tests and
  ``launch/train.py --kill-rank/--kill-at-step``.

Example (mock-level; the sim-transport end-to-end version lives in
``tests/test_elastic.py``)::

    trainer = Trainer(cfg, tcfg, mesh, batch=8, seq=128,
                      ckpt_dir="/tmp/ckpt", elastic=True,
                      fault_injector=lambda step: [1] if step == 7 else [])
    params, opt = trainer.init_state()
    params, opt, history = trainer.run(params, opt, steps=20)
    trainer.heals  # -> [{"survivors": ..., "dp": ..., "step": ...}]
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Sequence

import jax
import numpy as np

from ..checkpoint import CheckpointManager
from ..data.pipeline import DataConfig, Pipeline, synthetic_batch
from ..models import lm
from ..models.config import ModelConfig
from ..runtime import ElasticController, GroupError, Membership, StragglerPolicy
from .train_step import TrainConfig, init_opt_state, make_train_step


@dataclass
class Trainer:
    cfg: ModelConfig
    tcfg: TrainConfig
    mesh: object
    batch: int
    seq: int
    multi_pod: bool = False
    ckpt_dir: str = ""
    ckpt_every: int = 50
    data_cfg: DataConfig = field(default_factory=DataConfig)
    log_every: int = 10
    elastic: bool = False
    regroup: str = "pow2_floor"  # build_group strategy for heals
    make_mesh: Callable[[int], object] | None = None
    fault_injector: Callable[[int], Sequence[int]] | None = None

    def __post_init__(self):
        self._build_step()
        self.ckpt = CheckpointManager(self.ckpt_dir) if self.ckpt_dir else None
        self._membership_reset()
        self.controller = ElasticController(
            membership=self.membership,
            rebuild=self._rebuild,
            restore=self._restore,
            strategy=self.regroup,
        ) if self.elastic else None
        self._restored_state = None

    # -- construction helpers ----------------------------------------------
    def _build_step(self):
        self.step_fn, self.ax, self.pspecs = make_train_step(
            self.cfg, self.tcfg, self.mesh, self.multi_pod
        )

    def _membership_reset(self):
        n_ranks = int(np.prod(self.mesh.devices.shape))
        self.membership = Membership(expected=n_ranks)
        self.straggler = StragglerPolicy(n_ranks=n_ranks)
        for r in range(n_ranks):
            self.membership.join(r)

    def init_state(self, seed: int = 0):
        from .train_step import place_state

        with jax.set_mesh(self.mesh):
            params = lm.init_params(self.cfg, jax.random.key(seed))
            opt = init_opt_state(self.cfg, self.tcfg, params)
            params, opt = place_state(self.mesh, params, opt, self.pspecs, self.tcfg)
        return params, opt

    # -- elastic callbacks (regroup / reshard halves of a heal) -------------
    def _rebuild(self, dp: int):
        """Regroup: rebuild mesh + step function at the new degree."""
        if self.make_mesh is not None:
            self.mesh = self.make_mesh(dp)
        self._build_step()
        n_ranks = int(np.prod(self.mesh.devices.shape))
        self.straggler = StragglerPolicy(n_ranks=n_ranks)

    def _restore(self) -> int:
        """Reshard: latest committed checkpoint re-placed onto the rebuilt
        mesh (falls back to re-initialization at step 0 when nothing was
        committed yet)."""
        with jax.set_mesh(self.mesh):
            if self.ckpt is not None:
                self.ckpt.wait()
                try:
                    pshapes = jax.eval_shape(
                        lambda: lm.init_params(self.cfg, jax.random.key(0))
                    )
                    oshapes = jax.eval_shape(
                        lambda: init_opt_state(self.cfg, self.tcfg, pshapes)
                    )
                    state, step = self.ckpt.restore_latest(
                        {"params": pshapes, "opt": oshapes}
                    )
                    self._restored_state = (state["params"], state["opt"])
                    return step
                except FileNotFoundError:
                    pass
            self._restored_state = self.init_state()
            return 0

    @property
    def heals(self) -> list:
        """History of committed heals (empty when not elastic)."""
        return self.controller.history if self.controller else []

    # -- the loop -----------------------------------------------------------
    def _beat(self, step: int):
        """Heartbeat every current-group rank, then apply injected faults
        (the deterministic stand-in for ranks going silent)."""
        for r in sorted(self.membership.group()):
            self.membership.heartbeat(r)
        if self.fault_injector is not None:
            for r in self.fault_injector(step):
                self.membership.mark_failed(int(r))

    def run(self, params, opt_state, steps: int, start_step: int = 0):
        """Run ``steps`` steps (elastic mode: *productive* steps — a healed
        step re-executes from the restored checkpoint step)."""
        history = []
        step, end = start_step, start_step + steps
        while step < end:
            if self.controller is not None:
                try:
                    self._beat(step)
                    self.membership.check_alive()
                except GroupError:
                    resume = self.controller.heal()
                    params, opt_state = self._restored_state
                    self._restored_state = None
                    step = resume
                    continue
            with jax.set_mesh(self.mesh):
                batch = synthetic_batch(
                    self.data_cfg, self.cfg, self.batch, self.seq, step
                )
                batch = jax.tree.map(jax.numpy.asarray, batch)
                t0 = time.perf_counter()
                params, opt_state, metrics = self.step_fn(params, opt_state, batch)
                metrics = jax.tree.map(float, jax.device_get(metrics))
                dt = time.perf_counter() - t0
                self.straggler.observe(0, dt)
                history.append({"step": step, "time_s": dt, **metrics})
                if self.ckpt and (step + 1) % self.ckpt_every == 0:
                    self.ckpt.save_async(
                        {"params": params, "opt": opt_state}, step + 1,
                        extra={
                            "generation": self.controller.generation
                            if self.controller else 0,
                            "world": len(self.membership.group()),
                        },
                    )
            step += 1
        if self.ckpt:
            self.ckpt.wait()
        return params, opt_state, history
