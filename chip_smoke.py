#!/usr/bin/env python3
"""Drive the FMI training path once on a TPU and check what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one four-chip host

One chip: every Pallas kernel on a small input against its oracle in
``repro.kernels.ref``, then llama3.2-1b at its published widths trained for
five steps through ``repro.launch.train`` (fmi mode, bf16 Adam moments,
batch 1 x 2048).  The loss must be finite and fall, and the step must hold
the Mosaic flash-attention kernel.

Four chips: llama3.2-1b at full width, fmi-mode ZeRO-1 gradient sync
against xla mode from the same seed and batches; then one FMI allreduce
(ring, recursive doubling) of a gradient-sized buffer against XLA's.

All phases run in this one process, which owns the chips.  Every time and
byte count printed is a smoke reading, not a benchmark.  The last line of
standard output is one JSON object naming the device; it is printed only
when every phase passed.  Without a TPU the script exits non-zero and
prints no result.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "llama3.2-1b"
STEPS = 5
SEQ = 2048
ALLREDUCE_ELEMS = 16 * 2048 * 8192  # one f32 gradient of the stacked MLP up-projections
# at full width the launcher's default 3e-4 with its 2-step warmup throws a
# freshly initialised model off (measured on a v5e: loss 12.5 -> 9.96 ->
# 15.4); an Adam step moves each output by about lr x fan-in
LR = 3e-5
# fmi ZeRO-1 and xla mode run the same bf16 model from the same seed and
# batches; they differ in the order of their f32 gradient sums, which Adam
# amplifies a little each step.  Two bf16 ulps of the loss bound that.
LOSS_RTOL = 2 * 2.0**-8


def smoke(label: str, value) -> None:
    print(f"smoke reading (not a benchmark): {label} = {value}")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAIL: {what}")
    print(f"ok: {what}")


def close(got, want, atol: float, what: str) -> None:
    import numpy as np

    err = float(np.max(np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))))
    check(bool(np.isfinite(err)) and err <= atol, f"{what} (max |err| {err:.3g} <= {atol})")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def kernels_phase() -> None:
    """Each Pallas kernel compiled for the chip against its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.quantize import (dequantize_blockwise, dequantize_page,
                                        quantize_blockwise, quantize_page)
    from repro.kernels.ssm_scan import gla_scan

    rng = np.random.default_rng(0)

    def mk(shape, dtype=jnp.float32):
        return jnp.asarray(rng.normal(size=shape), dtype)

    with jax.default_matmul_precision("highest"):
        # flash attention forward and gradient (llama3.2-1b head layout)
        q, k, v = mk((1, 32, 512, 64), jnp.bfloat16), mk((1, 8, 512, 64), jnp.bfloat16), mk((1, 8, 512, 64), jnp.bfloat16)
        close(ops.flash_attention(q, k, v, backend="pallas"),
              ref.attention(q, k, v), 3e-2, "flash_attention forward vs oracle")

        def loss(backend):
            return lambda q, k, v: jnp.sum(
                ops.flash_attention(q, k, v, backend=backend).astype(jnp.float32) ** 2)

        g_kernel = jax.jit(jax.grad(loss("pallas"), argnums=(0, 1, 2)))(q, k, v)
        g_xla = jax.jit(jax.grad(loss("xla"), argnums=(0, 1, 2)))(q, k, v)
        for name, a, b in zip("qkv", g_kernel, g_xla):
            scale = float(jnp.max(jnp.abs(b.astype(jnp.float32))))
            close(a, b, 3e-2 * scale, f"flash_attention d{name} vs xla twin")

        # paged attention over f32 and int8 pools (qwen3-1.7b head layout)
        n_pages, ps, hkv, d, B, hq, npm = 32, 16, 8, 128, 4, 16, 6
        qd = mk((B, hq, d))
        kp, vp = mk((n_pages, ps, hkv, d)), mk((n_pages, ps, hkv, d))
        table = jnp.asarray(np.stack([rng.choice(n_pages, npm, replace=False) for _ in range(B)]), jnp.int32)
        lengths = jnp.asarray(rng.integers(1, npm * ps + 1, size=B), jnp.int32)
        close(paged_attention(qd, kp, vp, table, lengths),
              ref.paged_attention(qd, kp, vp, table, lengths), 2e-2,
              "paged_attention f32 pool vs oracle")
        kq, ks = quantize_page(kp)
        vq, vs = quantize_page(vp)
        close(paged_attention(qd, kq, vq, table, lengths, k_scale=ks, v_scale=vs),
              ref.paged_attention(qd, kq, vq, table, lengths, k_scale=ks, v_scale=vs),
              2e-2, "paged_attention int8 pool vs oracle")

        # page and blockwise quantization round trips
        kq_ref, ks_ref = ref.quantize_page(kp)
        check(int(jnp.max(jnp.abs(kq.astype(jnp.int32) - kq_ref.astype(jnp.int32)))) <= 1,
              "quantize_page codes within one step of the oracle")
        close(dequantize_page(kq, ks), kp, float(jnp.max(ks)), "dequantize_page round trip")
        x = mk((8, 65536))
        xq, xs = quantize_blockwise(x)
        xq_ref, _ = ref.quantize_blockwise(x, 256)
        check(int(jnp.max(jnp.abs(xq.astype(jnp.int32) - xq_ref.astype(jnp.int32)))) <= 1,
              "quantize_blockwise codes within one step of the oracle")
        close(dequantize_blockwise(xq, xs), x, float(jnp.max(xs)), "dequantize_blockwise round trip")

        # gated linear attention scan (xlstm-125m: 4 heads, head dim 192)
        qg, kg, vg = mk((1, 4, 512, 192)), mk((1, 4, 512, 192)), mk((1, 4, 512, 192))
        lf = jnp.asarray(-np.abs(rng.normal(size=(1, 4, 512)) * 0.5), jnp.float32)
        ig = jnp.asarray(np.abs(rng.normal(size=(1, 4, 512))), jnp.float32)
        out, _ = gla_scan(qg, kg, vg, lf, ig)
        close(out, ref.gla_scan(qg, kg, vg, lf, ig), 2e-2, "gla_scan vs per-step oracle")


def train_phase() -> None:
    """Full-width llama3.2-1b, fmi mode, five steps on one chip."""
    import jax

    from repro.launch import train

    run = train.main([
        "--arch", ARCH, "--mode", "fmi", "--data-axis", "1",
        "--steps", str(STEPS), "--batch", "1", "--seq", str(SEQ),
        "--moment-dtype", "bfloat16", "--lr", str(LR), "--log-every", "1",
    ])
    hist = run["history"]
    losses = [h["loss"] for h in hist]
    smoke("compile seconds", f"{run['compile_s']:.3f}")
    for h in hist[1:]:
        smoke(f"step {h['step']} seconds", f"{h['time_s']:.6f}")
    stats = jax.devices()[0].memory_stats() or {}
    smoke("peak_bytes_in_use", stats.get("peak_bytes_in_use", "not reported"))
    print(f"attention backend: {run['attention_backend']} for training and "
          f"prefill (static offset); decode with a traced offset runs xla; "
          f"Mosaic kernels in the step: {run['mosaic_calls']}")
    check(all(math.isfinite(x) for x in losses), f"finite losses {losses}")
    check(losses[-1] < losses[0], f"loss falls {losses[0]:.4f} -> {losses[-1]:.4f}")
    check(run["mosaic_calls"] > 0, "the train step runs the Mosaic flash kernel")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def zero1_vs_xla_phase() -> None:
    """fmi ZeRO-1 gradient sync against xla mode, same seed and batches."""
    from repro.launch import train

    common = ["--arch", ARCH, "--data-axis", "4", "--steps", str(STEPS),
              "--batch", "4", "--seq", str(SEQ), "--lr", str(LR), "--log-every", "1"]
    runs = {}
    for name, extra in (("fmi-zero1", ["--mode", "fmi", "--zero1"]),
                        ("xla", ["--mode", "xla"])):
        print(f"--- {name}")
        runs[name] = train.main(common + extra)
        smoke(f"{name} compile seconds", f"{runs[name]['compile_s']:.3f}")
        for h in runs[name]["history"][1:]:
            smoke(f"{name} step {h['step']} seconds", f"{h['time_s']:.6f}")
    fmi = [h["loss"] for h in runs["fmi-zero1"]["history"]]
    xla = [h["loss"] for h in runs["xla"]["history"]]
    for s, (a, b) in enumerate(zip(fmi, xla)):
        print(f"step {s}: loss fmi-zero1 {a:.6f} xla {b:.6f} |diff| {abs(a - b):.6f}")
    check(all(math.isfinite(a) and math.isfinite(b) for a, b in zip(fmi, xla)), "finite losses")
    check(all(abs(a - b) <= LOSS_RTOL * abs(b) for a, b in zip(fmi, xla)),
          f"fmi ZeRO-1 losses match xla mode within rtol {LOSS_RTOL}")
    check(fmi[-1] < fmi[0], f"loss falls {fmi[0]:.4f} -> {fmi[-1]:.4f}")
    for name in runs:
        check(runs[name]["mosaic_calls"] > 0, f"{name} step runs the Mosaic flash kernel")


def allreduce_phase() -> None:
    """FMI ring and recursive doubling against XLA's psum on a gradient-sized
    f32 buffer (1 GiB per chip)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import collectives as C
    from repro.core.communicator import Communicator

    n = ALLREDUCE_ELEMS
    mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
    comm = Communicator(axes=("data",), sizes=(4,), channel="ici")
    x = jax.jit(lambda k: jax.random.normal(k, (4, n), jnp.float32),
                out_shardings=NamedSharding(mesh, P("data")))(jax.random.key(0))
    outs = {}
    with jax.set_mesh(mesh):
        for algo in ("xla", "ring", "recursive_doubling"):
            f = jax.jit(jax.shard_map(
                lambda v, a=algo: C.allreduce(v[0], comm, algorithm=a)[None],
                in_specs=P("data"), out_specs=P("data"), check_vma=False))
            out = jax.block_until_ready(f(x))
            t0 = time.perf_counter()
            for _ in range(5):
                out = f(x)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / 5
            smoke(f"allreduce {algo} {n * 4} bytes/chip seconds per call", f"{dt:.6f}")
            outs[algo] = out
    want = outs.pop("xla")
    for algo, got in outs.items():
        err = float(jnp.max(jnp.abs(got - want)))
        check(err <= 1e-5, f"FMI {algo} allreduce matches XLA psum (max |err| {err:.3g})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip path and its comparison")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"need {want} chips, JAX found {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    phases = ((zero1_vs_xla_phase, allreduce_phase) if args.four_chips
              else (kernels_phase, train_phase))
    for phase in phases:
        print(f"=== {phase.__name__}")
        t0 = time.perf_counter()
        phase()
        smoke(f"{phase.__name__} seconds", f"{time.perf_counter() - t0:.3f}")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform, "kind": d.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
