"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
        --steps 50 --batch 8 --seq 128 --mode fmi --allreduce ring

``--reduced`` trains the CPU-sized config of the same family; without it
the published widths train, which on one 16 GB TPU v5e fits llama3.2-1b
with ``--moment-dtype bfloat16`` at batch 1 x 2048 (``chip_smoke.py``).
``main(argv)`` also runs in-process and returns the run's summary.
Supports both distribution modes, gradient compression, ZeRO-1,
checkpoint/restart (``--ckpt-dir``), and resumes automatically from the
latest committed checkpoint.

Profiling: ``--profile-dir DIR`` traces ``--profile-steps`` steps after the
first (which may compile) with ``jax.profiler`` into ``DIR``.  Each step is a
``train`` step annotation; the batch, checkpoint and heal paths are
``fmi.input``, ``fmi.checkpoint`` and ``fmi.heal`` spans on the trace's
clock, and the device ops carry the program's named scopes (``embed``,
``attention``, ``mlp``, ``flash_fwd``, ``flash_bwd``, ``head_loss``,
``optimizer``, ``fmi/<op>/<algorithm>``; docs/profiling.md).

Elastic demo: ``--elastic`` arms the runtime's heal path, and
``--kill-rank R --kill-at-step N`` injects a deterministic failure —
at step N rank R is declared dead, the :class:`ElasticController` runs
quiesce → regroup (``--regroup`` strategy) → reshard (latest committed
checkpoint, or re-init when none), and the loop resumes at the restored
step::

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
        --steps 20 --ckpt-dir /tmp/ck --ckpt-every 5 \
        --elastic --kill-rank 0 --kill-at-step 12
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np
from jax.sharding import NamedSharding

from .. import configs
from ..checkpoint import CheckpointManager
from ..data.pipeline import DataConfig, synthetic_batch
from ..kernels import ops
from ..models import lm
from ..optim.optimizer import OptConfig
from ..training.train_step import TrainConfig, init_opt_state, make_train_step, place_state
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mode", default="xla", choices=["xla", "fmi"])
    ap.add_argument("--allreduce", default="auto")
    ap.add_argument("--schedule", default="blocking", choices=["blocking", "bucketed"],
                    help="gradient sync: fused blocking collective vs "
                    "CommScheduler bucketed-overlap requests")
    ap.add_argument("--bucket-mb", type=float, default=None,
                    help="pin the scheduler bucket size (MB); default lets "
                    "selector.bucket_plan choose from the α-β model")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--compression", default="none", choices=["none", "int8"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--moment-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="Adam moment dtype; bfloat16 lets full-width "
                    "llama3.2-1b train on one 16 GB chip")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data-axis", type=int, default=1)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--out-json", default="")
    ap.add_argument("--profile-dir", default="",
                    help="trace --profile-steps steps after the first with "
                    "jax.profiler into this directory")
    ap.add_argument("--profile-steps", type=int, default=3)
    ap.add_argument("--elastic", action="store_true",
                    help="arm the elastic heal path (membership + controller)")
    ap.add_argument("--regroup", default="pow2_floor",
                    choices=["auto", "pow2_floor", "ring", "recursive_doubling"],
                    help="group-build strategy for heals (algorithms.build_group)")
    ap.add_argument("--kill-rank", type=int, default=None,
                    help="inject: declare this rank dead at --kill-at-step")
    ap.add_argument("--kill-at-step", type=int, default=None)
    from .sanitize_cli import add_sanitize_args, arm, emit

    add_sanitize_args(ap)
    args = ap.parse_args(argv)
    san = arm(args)  # before the first communicator is built
    enable_compile_cache()

    cfg = configs.get_reduced(args.arch) if args.reduced else configs.get(args.arch)
    mesh = make_host_mesh(args.data_axis, args.model_axis)
    tcfg = TrainConfig(
        mode=args.mode,
        microbatches=args.microbatches,
        optimizer=OptConfig(lr=args.lr, total_steps=args.steps,
                            warmup_steps=min(20, args.steps // 5 + 1),
                            state_dtype=args.moment_dtype),
        allreduce=args.allreduce,
        schedule=args.schedule,
        bucket_mb=args.bucket_mb,
        zero1=args.zero1,
        compression=args.compression,
    )
    step_fn, ax, pspecs = make_train_step(cfg, tcfg, mesh, multi_pod=False)
    dcfg = DataConfig()

    with jax.set_mesh(mesh):
        # params reach their shardings before the moments exist: at full
        # width f32 params and f32 moments do not fit one chip together
        params = jax.device_put(
            lm.init_params(cfg, jax.random.key(0)),
            jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs))
        if args.zero1 and args.mode == "fmi":
            from ..core.communicator import Communicator
            from ..training import zero1 as z1

            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            comm = Communicator(axes=ax.data, sizes=tuple(sizes[a] for a in ax.data))
            layout = z1.make_layout(params, comm.size)
            opt_state = z1.zero1_init(params, layout, comm, tcfg.optimizer.state_dtype)
        else:
            opt_state = init_opt_state(cfg, tcfg, params)
        params, opt_state = place_state(mesh, params, opt_state, pspecs, tcfg)

        start = 0
        ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        if ckpt is not None:
            try:
                state, start = ckpt.restore_latest({"params": params, "opt": opt_state})
                params, opt_state = state["params"], state["opt"]
                print(f"resumed from step {start}")
            except FileNotFoundError:
                pass

        # elastic runtime: membership + controller around the loop (heals
        # rebuild the step function and reshard from the latest commit)
        controller = None
        state = {"params": params, "opt": opt_state}
        if args.elastic:
            from ..runtime import ElasticController, GroupError, Membership

            n_ranks = args.data_axis * args.model_axis
            membership = Membership(expected=n_ranks)
            for r in range(n_ranks):
                membership.join(r)

            def rebuild(dp):
                nonlocal step_fn
                # single-host smoke path: the mesh keeps its devices; the
                # step function is rebuilt (multi-device rescale is
                # exercised by Trainer and tests/test_elastic.py)
                step_fn, _, _ = make_train_step(cfg, tcfg, mesh, multi_pod=False)

            def restore():
                if ckpt is not None:
                    ckpt.wait()
                    try:
                        target = {"params": state["params"], "opt": state["opt"]}
                        restored, s = ckpt.restore_latest(target)
                        state.update(restored)
                        return s
                    except FileNotFoundError:
                        pass
                print("heal: no committed checkpoint; continuing from live "
                      "state (bounded-staleness restart)")
                return state["step_cursor"]

            controller = ElasticController(
                membership=membership, rebuild=rebuild, restore=restore,
                strategy=args.regroup,
            )

        def batch_at(step):
            return jax.tree.map(
                jax.numpy.asarray,
                synthetic_batch(dcfg, cfg, args.batch, args.seq, step),
            )

        # compile once, ahead of the loop, so compile time is reported apart
        # from the step times and the compiled program can be inspected
        t0 = time.perf_counter()
        step_fn = step_fn.lower(params, opt_state, batch_at(start)).compile()
        compile_s = time.perf_counter() - t0
        mosaic_calls = step_fn.as_text().count('custom_call_target="tpu_custom_call"')
        print(f"compiled step in {compile_s:.1f}s; attention backend "
              f"{ops.flash_backend()}; Mosaic kernels in the step: {mosaic_calls}")

        history = []
        profiling = "off" if args.profile_dir else "done"

        def profile():
            """Start the trace after the first step; stop it
            ``--profile-steps`` steps later."""
            nonlocal profiling
            if profiling == "off" and len(history) == 1:
                jax.profiler.start_trace(args.profile_dir)
                profiling = "on"
            elif profiling == "on" and len(history) >= 1 + args.profile_steps:
                jax.profiler.stop_trace()
                profiling = "done"

        t_start = time.perf_counter()
        step, end = start, start + args.steps
        while step < end:
            profile()
            state["step_cursor"] = step
            if controller is not None:
                try:
                    for r in sorted(membership.group()):
                        membership.heartbeat(r)
                    if args.kill_rank is not None and step == args.kill_at_step:
                        membership.mark_failed(args.kill_rank)
                        args.kill_rank = None  # one-shot injection
                    membership.check_alive()
                except GroupError as e:
                    print(f"step {step:5d} FAILURE: {e}")
                    with jax.profiler.TraceAnnotation("fmi.heal"):
                        step = controller.heal()
                    params, opt_state = state["params"], state["opt"]
                    h = controller.history[-1]
                    print(f"healed: regrouped to dp={h['dp']} "
                          f"({h['strategy']}, spares={h['spares']}), "
                          f"resuming at step {step}")
                    continue
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                with jax.profiler.TraceAnnotation("fmi.input"):
                    batch = batch_at(step)
                t0 = time.perf_counter()
                params, opt_state, metrics = step_fn(params, opt_state, batch)
                jax.block_until_ready((params, opt_state, metrics))
                dt = time.perf_counter() - t0
            m = {k: float(v) for k, v in metrics.items()}
            history.append({"step": step, "time_s": dt, **m})
            state["params"], state["opt"] = params, opt_state
            if step % args.log_every == 0 or step == end - 1:
                print(f"step {step:5d} loss {m['loss']:.4f} ce {m['ce']:.4f} "
                      f"lr {m['lr']:.2e} gnorm {m.get('grad_norm', 0):.2f} {dt*1e3:.0f}ms")
            if ckpt is not None and (step + 1) % args.ckpt_every == 0:
                world = (len(membership.group()) if controller is not None
                         else args.data_axis * args.model_axis)
                with jax.profiler.TraceAnnotation("fmi.checkpoint"):
                    ckpt.save_async(
                        {"params": params, "opt": opt_state}, step + 1,
                        extra={"generation": controller.generation if controller
                               else 0, "world": world},
                    )
            step += 1
        if profiling == "on":
            jax.profiler.stop_trace()
        if ckpt is not None:
            ckpt.wait()

    total = time.perf_counter() - t_start
    first, last = history[0]["ce"], history[-1]["ce"]
    print(f"done: {args.steps} steps in {total:.1f}s; ce {first:.3f} -> {last:.3f}")
    if args.out_json:
        with open(args.out_json, "w") as f:
            json.dump(history, f)
    emit(san, args)
    return {"history": history, "compile_s": compile_s,
            "attention_backend": ops.flash_backend(),
            "mosaic_calls": mosaic_calls}


if __name__ == "__main__":
    main()
