"""One training cell: set-up, the measured window, and the check.

The window drives the program's own jitted step,
``repro.training.train_step.make_train_step(cfg, TrainConfig(mode="fmi", ...),
mesh)``, with its state built as ``repro.launch.train`` builds it.  Set-up
makes the weights on the device from the seed, compiles the step, and runs
the cell's first steps through the very call and feed that the window then
goes on with.  The check compares what those first steps did with the plain
reference, which runs after the window once the program's state is freed.

The cell's configuration file names the program's registry entry
(``program_arch``) and its plain reference (``reference``, under ``refs/``),
whose ``Sizes`` read the file's published keys and whose ``program_fields``
put them into the registry's entry.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import math
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core.communicator import Communicator
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.optim.optimizer import OptConfig, adamw_init
from repro.training import zero1
from repro.training.train_step import TrainConfig, make_train_step, place_state

from .. import catalog, compare, harness, traffic, weights
from ..refs.dense_lm import Readings


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: the registry's
    entry for ``program_arch`` with the fields that the file's reference
    gives for its sizes (``program_fields``) and the file's dtypes put in."""
    ref = catalog.reference(config)
    prec = config["precision"]
    return replace_fields(configs.get(config["program_arch"]), {
        **ref.program_fields(ref.Sizes.from_config(config)),
        "dtype": prec["compute_dtype"], "param_dtype": prec["param_dtype"]})


def replace_fields(obj, fields: dict):
    """``dataclasses.replace`` of ``obj`` by ``fields``, where a dict value
    replaces fields of the nested dataclass of that name in turn."""
    return dataclasses.replace(obj, **{
        k: replace_fields(getattr(obj, k), v) if isinstance(v, dict) else v
        for k, v in fields.items()})


def leaf_names(tree) -> list:
    paths, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path) for path, _ in paths]


class Run:
    """The program's training step, built once for a cell; ``start(seed)``
    gives it its state from the seed."""

    def __init__(self, cell: catalog.Cell, devices: list):
        self.cell = cell
        job = cell.traffic
        self.cfg = model_config(cell.config)
        self.sizes = cell.sizes
        opt = job["optimizer"]
        self.opt_cfg = OptConfig(
            lr=opt["lr"], beta1=opt["beta1"], beta2=opt["beta2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"], clip_norm=opt["clip_norm"],
            warmup_steps=opt["warmup_steps"], total_steps=opt["total_steps"],
            min_lr_frac=opt["min_lr_frac"], state_dtype=opt["moment_dtype"])
        self.tcfg = TrainConfig(mode=job["step"]["mode"], zero1=job["step"]["zero1"],
                                optimizer=self.opt_cfg)
        self.zero1 = self.tcfg.zero1
        self.ranks = len(devices)
        self.mesh = make_host_mesh(self.ranks, 1)
        self.step_fn, ax, self.pspecs = make_train_step(self.cfg, self.tcfg, self.mesh)
        self.tokens_per_step = self.ranks * job["rows_per_chip"] * job["seq_len"]
        self.batch_sharding = NamedSharding(self.mesh, P(ax.data))
        rep = NamedSharding(self.mesh, P())

        pshapes = jax.eval_shape(lambda: lm.init_params(self.cfg, jax.random.key(0)))
        self.names = leaf_names(pshapes)
        shapes = {n: tuple(x.shape) for n, x in zip(self.names, jax.tree.leaves(pshapes))}
        ref_shapes = catalog.reference(cell.config).layout(self.sizes)
        if shapes != ref_shapes:
            raise ValueError(f"program leaves {shapes} differ from the reference's {ref_shapes}")
        treedef = jax.tree.structure(pshapes)
        pdtype = self.cfg.pdtype

        def init(key):
            return jax.tree.unflatten(treedef, [
                weights.leaf(key, n, shapes[n], pdtype) for n in self.names])

        self._init_params = jax.jit(init, out_shardings=rep)
        if self.zero1:
            axis_sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
            comm = Communicator(axes=ax.data, sizes=tuple(axis_sizes[a] for a in ax.data))
            layout = zero1.make_layout(pshapes, comm.size)
            self._init_opt = jax.jit(lambda p: zero1.zero1_init(
                pshapes, layout, comm, self.opt_cfg.state_dtype), out_shardings=rep)
        else:
            self._init_opt = jax.jit(lambda p: adamw_init(p, self.opt_cfg),
                                     out_shardings=rep)

        # readers of the program's state: each chip's share of the first
        # moments (ZeRO-1 keeps a different row block on each chip), and each
        # leaf's change from its initial value, made again from the seed
        self._moment_sq = jax.jit(jax.shard_map(
            lambda m: jnp.stack([jnp.sum(jnp.square(x.astype(jnp.float32)))
                                 for x in jax.tree.leaves(m)])[None],
            mesh=self.mesh, in_specs=P(), out_specs=P(ax.data), check_vma=False))
        self._change = jax.jit(lambda key, params: jnp.stack([
            jnp.linalg.norm(p.astype(jnp.float32)
                            - weights.leaf(key, n, shapes[n], pdtype).astype(jnp.float32))
            for n, p in zip(self.names, jax.tree.leaves(params))]))
        self.params = self.opt_state = None

    def start(self, seed: int):
        """The seed's initial state, placed as the launcher places it."""
        self.free()
        self.seed, self.key = seed, weights.root_key(seed)
        params = self._init_params(self.key)
        self.params, self.opt_state = place_state(self.mesh, params, self._init_opt(params),
                                                  self.pspecs, self.tcfg)

    def feed(self, step: int) -> dict:
        """Step ``step``'s batch, made on the host and sent to the chips."""
        host = traffic.batch(self.cell.traffic, self.sizes.vocab, self.seed, step, self.ranks)
        return jax.device_put(host, self.batch_sharding)

    def step(self, batch):
        self.params, self.opt_state, metrics = self.step_fn(self.params, self.opt_state, batch)
        return metrics

    def first_steps(self) -> Readings:
        """Run the checked first steps through the window's own call and feed,
        reading what the check needs as it goes."""
        losses, grad_norms = [], None
        b1 = self.opt_cfg.beta1
        for k in range(self.cell.traffic["checked_steps"]):
            losses.append(self.step(self.feed(k))["loss"])
            if k == 0:
                sq = np.asarray(self._moment_sq(self.opt_state["m"]))
                sq = sq.sum(0) if self.zero1 else sq[0]
                grad_norms = dict(zip(self.names, (np.sqrt(sq) / (1 - b1)).tolist()))
        change = dict(zip(self.names, np.asarray(self._change(self.key, self.params)).tolist()))
        return Readings([float(x) for x in losses], grad_norms, change)

    def free(self):
        self.params = self.opt_state = None
        gc.collect()


def window(run: Run, seconds: float, first_step: int, spans: harness.Spans) -> dict:
    """Dispatch steps back to back for ``seconds``; the host keeps at most
    two steps queued ahead of the one it waits for."""
    pending = collections.deque()
    step = first_step
    t0 = time.perf_counter()
    while True:
        with spans("input"):
            batch = run.feed(step)
        with spans("dispatch"):
            pending.append(run.step(batch)["loss"])
        step += 1
        if len(pending) > 2:
            with spans("wait"):
                pending.popleft().block_until_ready()
        if time.perf_counter() - t0 >= seconds:
            break
    with spans("wait"):
        jax.block_until_ready((run.params, run.opt_state, pending[-1]))
    t1 = time.perf_counter()
    losses = [float(x) for x in pending]
    return {"steps": step - first_step, "seconds": t1 - t0, "last_losses": losses}


def run_cell(cell: catalog.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices: list) -> dict:
    """One run of ``cell`` on ``devices``, its set-up timed from ``t_start``:
    returns the result object of the run's last line."""
    phases = {"start": time.perf_counter() - t_start}
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    kind = devices[0].device_kind
    run = Run(cell, devices)
    phases["build"] = time.perf_counter() - t_start
    run.start(seed)
    jax.block_until_ready((run.params, run.opt_state))
    phases["state"] = time.perf_counter() - t_start
    program = run.first_steps()
    compiles = run.step_fn._cache_size()
    setup_s = time.perf_counter() - t_start
    phases["first_steps"] = setup_s
    print("setup " + " ".join(f"{k}={v:.3f}" for k, v in phases.items()), file=sys.stderr)

    spans = harness.Spans()
    first = cell.traffic["checked_steps"]
    tracedir = tempfile.TemporaryDirectory() if trace else contextlib.nullcontext()
    with tracedir as tdir:
        if trace:
            jax.profiler.start_trace(tdir)
        w = window(run, seconds, first, spans)
        if trace:
            jax.profiler.stop_trace()
        if run.step_fn._cache_size() != compiles:
            raise RuntimeError("the step compiled again inside the measured window")
        mem = harness.memory_peak_bytes(devices)
        tokens_per_s = w["steps"] * run.tokens_per_step / w["seconds"]
        window_losses = w["last_losses"]
        metrics, device_extra, breakdown = {}, {}, None
        if trace:
            from .. import trace as tr

            t = tr.load(tdir, len(devices))
            ctx = tr.Context(trace=t, cell=cell, spans=spans, window=w,
                             tokens_per_s=tokens_per_s, peaks=catalog.peaks(kind),
                             profile=tdir)
            metrics = harness.per_layer(cell, ctx)
            device_extra = {"busy_s": t.busy_s(), "window_s": t.window_s()}
            breakdown = t.breakdown()
        else:
            values = {"tokens_per_s": tokens_per_s, "setup_s": setup_s}
            for m in cell.end_to_end:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    run.free()
    del run

    ref = default_reference(cell, devices)
    checked = [traffic.batch(cell.traffic, cell.sizes.vocab, seed, k, len(devices))
               for k in range(first)]
    reference = ref.run(seed, checked)
    gaps = compare.gaps(program, reference)
    finite = all(math.isfinite(x) for x in window_losses)
    correct = finite and compare.within(gaps, cell.limits)
    result = {
        "correct": bool(correct),
        "attempted": first + w["steps"],
        "failed": 0 if finite else first + w["steps"],
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": kind, "count": len(devices),
                   "memory_peak_bytes": mem, **device_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = compare.report(gaps, cell.limits, finite)
    return result


def default_reference(cell: catalog.Cell, devices: list, mode: str = "f32"):
    """The configuration's plain reference; ``mode="fp8"`` is the control."""
    mod = catalog.reference(cell.config)
    return mod.Reference(cell.sizes, cell.traffic, cell.config["precision"], devices, mode)
