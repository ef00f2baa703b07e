"""Training step builders — the two distribution modes the paper contrasts.

``mode='xla'`` (provider channel, baseline): one ``jax.jit`` over the global
batch; parameters FSDP+TP-sharded via ``param_specs``; every collective is
inserted by GSPMD.  This is the "cloud-provider-managed communication" the
paper's mediated channels correspond to.

``mode='fmi'`` (the paper's technique): ``jax.shard_map`` manual over the
data axes (``('pod','data')`` across pods), auto (GSPMD) over 'model'.
Gradients are synchronized by an **explicit FMI collective** chosen by the
model-driven selector — ring / recursive-doubling / Rabenseifner /
hierarchical(ICI+DCN) / int8-compressed — and the optimizer runs either
replicated or as explicit ZeRO-1 (reduce-scatter + sharded update +
allgather built from FMI primitives).

Gradient accumulation: ``microbatches > 1`` runs a ``lax.scan`` of
forward/backward over microbatch slices before the single gradient
synchronization — communication amortized over the accumulation window
(compute/comm overlap trick #1; hierarchical + compression are #2/#3).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..core import collectives as C
from ..core import compression as COMP
from ..core.communicator import Communicator
from ..core.hierarchical import hierarchical_allreduce
from ..models import lm
from ..models.config import ModelConfig
from ..models.layers import Axes
from ..optim.optimizer import OptConfig, adamw_init, adamw_update, clip_by_global_norm
from . import zero1


@dataclass(frozen=True)
class TrainConfig:
    mode: str = "xla"  # 'xla' | 'fmi'
    microbatches: int = 1
    optimizer: OptConfig = field(default_factory=OptConfig)
    # fmi-mode knobs
    allreduce: str = "auto"  # auto|ring|recursive_doubling|rabenseifner|xla
    hierarchical: bool = False  # two-level (pod=DCN, data=ICI) reduction
    compression: str = "none"  # none | int8
    zero1: bool = False  # explicit ZeRO-1 over the data axis
    donate: bool = True
    # gradient-sync scheduling: 'blocking' = one fused allreduce_tree after
    # backward; 'bucketed' = per-layer requests coalesced by CommScheduler
    # into α-β-model-sized buckets and drained with overlap
    schedule: str = "blocking"  # 'blocking' | 'bucketed'
    bucket_mb: float | None = None  # pin the bucket size (MB); None = planner
    overlap_window_s: float = 0.0  # modeled backward window buckets can hide in


def _axes_for(cfg: ModelConfig, mesh, multi_pod: bool, global_batch=None) -> Axes:
    from ..launch.policy import axes_for

    return axes_for(cfg, mesh, multi_pod, "train", global_batch=global_batch)


def _loss(params, cfg: ModelConfig, ax: Axes, batch):
    logits, aux, _ = lm.forward(params, cfg, ax, batch)
    loss, ce = lm.loss_fn(logits, batch["labels"], cfg, aux)
    return loss, ce


def _grad_accum(params, cfg, ax, batch, microbatches: int):
    """Mean loss/grads over ``microbatches`` slices of the batch's leading dim."""
    if microbatches == 1:
        (loss, ce), grads = jax.value_and_grad(_loss, has_aux=True)(
            params, cfg, ax, batch
        )
        return loss, ce, grads

    def slice_mb(i, x):
        mb = x.shape[0] // microbatches
        return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)

    def body(carry, i):
        loss_a, ce_a, g_a = carry
        mb = jax.tree.map(functools.partial(slice_mb, i), batch)
        (loss, ce), g = jax.value_and_grad(_loss, has_aux=True)(params, cfg, ax, mb)
        return (loss_a + loss, ce_a + ce, jax.tree.map(jnp.add, g_a, g)), None

    zeros_g = jax.tree.map(jnp.zeros_like, params)
    (loss, ce, grads), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(()), zeros_g), jnp.arange(microbatches)
    )
    inv = 1.0 / microbatches
    return loss * inv, ce * inv, jax.tree.map(lambda g: g * inv, grads)


# ---------------------------------------------------------------------------
# xla mode
# ---------------------------------------------------------------------------


def make_train_step_xla(cfg: ModelConfig, tcfg: TrainConfig, mesh, multi_pod: bool,
                        global_batch: int | None = None):
    ax = _axes_for(cfg, mesh, multi_pod, global_batch)
    pspecs = lm.param_specs(cfg, ax, ax.sizes)

    def step(params, opt_state, batch):
        loss, ce, grads = _grad_accum(params, cfg, ax, batch, tcfg.microbatches)
        new_params, new_opt, om = adamw_update(grads, opt_state, params, tcfg.optimizer)
        return new_params, new_opt, {"loss": loss, "ce": ce, **om}

    in_shardings = (
        jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
        jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            jax.tree.map(lambda s: s, _opt_specs(cfg, ax, tcfg)),
        ),
        jax.tree.map(
            lambda s: NamedSharding(mesh, s), lm.input_spec_shardings(cfg, ax)
        ),
    )
    out_shardings = (
        in_shardings[0],
        in_shardings[1],
        NamedSharding(mesh, P()),
    )
    donate = (0, 1) if tcfg.donate else ()
    return (
        jax.jit(
            step,
            in_shardings=in_shardings,
            out_shardings=out_shardings,
            donate_argnums=donate,
        ),
        ax,
        pspecs,
    )


def _opt_specs(cfg: ModelConfig, ax: Axes, tcfg: TrainConfig):
    pspecs = lm.param_specs(cfg, ax, ax.sizes)
    return {
        "m": pspecs,
        "v": pspecs,
        "step": P(),
    }


def init_opt_state(cfg: ModelConfig, tcfg: TrainConfig, params):
    return adamw_init(params, tcfg.optimizer)


def place_state(mesh, params, opt_state, pspecs, tcfg: TrainConfig):
    """device_put freshly-initialized state onto the shardings the built
    step expects (jit rejects committed arrays with mismatched shardings
    on multi-device meshes)."""
    ns = lambda s: NamedSharding(mesh, s)  # noqa: E731
    params = jax.device_put(params, jax.tree.map(ns, pspecs))
    if tcfg.mode == "xla":
        ospecs = {"m": pspecs, "v": pspecs, "step": P()}
        opt_state = jax.device_put(opt_state, jax.tree.map(ns, ospecs))
    else:
        opt_state = jax.device_put(
            opt_state, jax.tree.map(lambda _: ns(P()), opt_state)
        )
    return params, opt_state


def eval_opt_shapes(cfg: ModelConfig, tcfg: TrainConfig, mesh, multi_pod: bool,
                    global_batch: int | None = None):
    """ShapeDtypeStructs of the optimizer state the built step expects
    (ZeRO-1 states are flat per-dtype chunks, not param-shaped)."""
    pshapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.key(0)))
    if tcfg.mode == "fmi" and tcfg.zero1:
        from ..launch.policy import plan

        pol = plan(cfg, mesh, multi_pod, "train", global_batch=global_batch)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        comm = Communicator(axes=pol.data, sizes=tuple(sizes[a] for a in pol.data))
        layout = zero1.make_layout(pshapes, comm.size)
        return jax.eval_shape(
            lambda: zero1.zero1_init(pshapes, layout, comm, tcfg.optimizer.state_dtype)
        )
    return jax.eval_shape(lambda: adamw_init(pshapes, tcfg.optimizer))


# ---------------------------------------------------------------------------
# fmi mode
# ---------------------------------------------------------------------------


def make_train_step_fmi(cfg: ModelConfig, tcfg: TrainConfig, mesh, multi_pod: bool,
                        global_batch: int | None = None):
    """shard_map manual over data axes; explicit FMI gradient collectives."""
    from ..launch.policy import plan

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    pol = plan(cfg, mesh, multi_pod, "train", global_batch=global_batch)
    data_axes = pol.data
    # inside the shard_map body the data axes are manual: activations carry
    # no data-axis sharding constraints (they are local), model stays auto
    ax_in = Axes(data=(), model=pol.model, fsdp=(), enabled=pol.model is not None,
                 sizes=sizes)
    comm_data = Communicator(axes=data_axes, sizes=tuple(sizes[a] for a in data_axes),
                             channel="ici")
    inner_axes = tuple(a for a in data_axes if a != "pod")
    comm_inner = Communicator(
        axes=inner_axes, sizes=tuple(sizes[a] for a in inner_axes), channel="ici"
    )
    comm_pod = (
        Communicator(axes=("pod",), sizes=(sizes["pod"],), channel="dcn")
        if multi_pod and "pod" in data_axes
        else None
    )

    layout = None
    if tcfg.zero1:
        pshapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.key(0)))
        layout = zero1.make_layout(pshapes, comm_data.size)

    def reduce_grads(grads):
        if tcfg.compression == "int8":
            t = comm_data.transport()
            lay = zero1.make_flat_layout(grads)
            flats = zero1.flatten_groups(grads, lay)
            out = []
            for f in flats:
                n = f.shape[0]
                padded = (-n) % (comm_data.size * 256)
                f2 = jnp.concatenate([f, jnp.zeros((padded,), f.dtype)]) if padded else f
                r = COMP.compressed_ring_allreduce(
                    t, f2.astype(jnp.float32), op="add", block=256, mean=True
                )
                out.append(r[:n].astype(f.dtype))
            return zero1.unflatten_groups(out, lay)
        if tcfg.hierarchical and comm_pod is not None:
            def one(g):
                shape = g.shape
                flat, n = g.reshape(-1), g.size
                pad = (-n) % comm_inner.size
                if pad:
                    flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
                red = hierarchical_allreduce(flat, comm_inner, comm_pod)
                return (red[:n] / comm_data.size).reshape(shape)

            return jax.tree.map(one, grads)
        # blocking: one fused collective per dtype after backward finishes;
        # bucketed: per-layer gradient requests through the CommScheduler
        # (issued in backward order, bucket size from selector.bucket_plan)
        return C.allreduce_tree(
            grads, comm_data, op="add", algorithm=tcfg.allreduce, mean=True,
            schedule=tcfg.schedule,
            bucket_bytes=(None if tcfg.bucket_mb is None
                          else int(tcfg.bucket_mb * 1e6)),
            compute_s=tcfg.overlap_window_s,
        )

    def local_step(params, opt_state, batch):
        loss, ce, grads = _grad_accum(params, cfg, ax_in, batch, tcfg.microbatches)
        if tcfg.zero1:
            # NOTE: zero1_update's reduce-scatter performs the gradient sync;
            # global-norm clipping happens inside, on the reduced chunks
            new_params, new_opt, om = zero1.zero1_update(
                grads, opt_state, params, layout, comm_data, tcfg.optimizer
            )
        else:
            grads = reduce_grads(grads)
            new_params, new_opt, om = adamw_update(
                grads, opt_state, params, tcfg.optimizer
            )
        loss = C.allreduce(loss[None], comm_data, algorithm="recursive_doubling")[0]
        ce = C.allreduce(ce[None], comm_data, algorithm="recursive_doubling")[0]
        inv = 1.0 / comm_data.size
        return new_params, new_opt, {"loss": loss * inv, "ce": ce * inv, **om}

    batch_specs = jax.tree.map(
        lambda _: P(data_axes), lm.input_spec_shardings(cfg, Axes(data=data_axes, sizes=sizes))
    )
    # params replicated over the (manual) data axes; model-axis sharding is
    # carried by the arrays themselves (auto axes pass through shard_map)
    rep = P()

    def spec_tree(tree):
        return jax.tree.map(lambda _: rep, tree)

    pshapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.key(0)))
    if tcfg.zero1:
        opt_shapes = jax.eval_shape(
            lambda: zero1.zero1_init(pshapes, layout, comm_data, tcfg.optimizer.state_dtype)
        )
    else:
        opt_shapes = jax.eval_shape(lambda: adamw_init(pshapes, tcfg.optimizer))

    step = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(spec_tree(pshapes), spec_tree(opt_shapes), batch_specs),
        out_specs=(
            spec_tree(pshapes),
            spec_tree(opt_shapes),
            {"loss": rep, "ce": rep, "lr": rep, "grad_norm": rep},
        ),
        axis_names=set(data_axes),
        check_vma=False,
    )
    jitted = jax.jit(step, donate_argnums=(0, 1) if tcfg.donate else ())
    ax_out = Axes(data=data_axes, model="model", fsdp="", enabled=True, sizes=sizes)
    return jitted, ax_out, jax.tree.map(lambda _: rep, pshapes)


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh, multi_pod: bool = False,
                    global_batch: int | None = None):
    if tcfg.mode == "xla":
        return make_train_step_xla(cfg, tcfg, mesh, multi_pod, global_batch)
    if tcfg.mode == "fmi":
        return make_train_step_fmi(cfg, tcfg, mesh, multi_pod, global_batch)
    raise ValueError(f"unknown mode {tcfg.mode!r}")
