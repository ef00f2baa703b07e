"""Serving launcher: FMI continuous batching (default) or mesh wave batching.

The continuous policy drives
:class:`repro.serving.engine.ContinuousBatchingEngine` — the
tensor-parallel runtime with a rank-sharded paged KV cache, per-step
admit/evict, explicit decode collectives through the request layer, and
elastic kill-rank recovery (see ``docs/serving.md``).  The wave policy is
the legacy jax path (:class:`repro.serving.engine.ServeEngine`) on the
reduced configs.

    # serve 16 requests through the TP engine on 4 simulated ranks:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --batch-policy continuous --tp 4 --requests 16 --batch 4 \
        --kv-pages 64 --max-new 16

    # what will a step cost?  the serve_plan tables for both regimes:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --explain

    # kill rank 3 mid-decode and watch the engine heal:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --tp 4 --kill-rank 3 --kill-at-step 2

    # CI smoke (tiny end-to-end run, exits 0):
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --dry-run

    # a 2-replica fleet replaying a seeded Poisson trace, autoscaling to 4
    # against a 30ms p99 SLO on the virtual clock:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --fleet 2 --autoscale --max-replicas 4 --slo-p99-ms 30 \
        --rate-rps 300 --duration-s 0.05

    # scale-up vs scale-out priced at the SLO (the fleet_plan table):
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --fleet 2 --slo-p99-ms 50 --explain
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from .. import configs
from ..serving.engine import ContinuousBatchingEngine
from ..serving.tp_lm import TPServeConfig
from .compile_cache import enable_compile_cache


def _tp_config(cfg, prompt_len: int, max_new: int) -> TPServeConfig:
    """Map a reduced arch config onto the TP serving model's shape (the
    sim-channel engine mirrors the reduced dims; the full model serves on
    the mesh path)."""
    r = cfg.reduced()
    return TPServeConfig(
        vocab_size=r.vocab_size, d_model=r.d_model, n_heads=r.n_heads,
        head_dim=r.hd, d_ff=r.d_ff, n_layers=r.n_layers,
        max_len=prompt_len + max_new, ff_chunks=max(4, r.n_heads),
    )


def _explain(cfg, args) -> None:
    from ..core.selector import explain_serve_plan

    print(f"production serve plan for {cfg.name} "
          f"(full config, {args.channel} channel):\n")
    print(explain_serve_plan(
        cfg.d_model, cfg.n_layers, cfg.vocab_size, P=args.tp * 4,
        batch=args.batch * 4, prompt_len=args.prompt_len * 64,
        channels=(args.channel,), logits_mode=args.logits_mode,
    ))
    scfg = _tp_config(cfg, args.prompt_len, args.max_new)
    print(f"\nreduced engine plan (what this launcher runs, "
          f"sim channel, tp={args.tp}):\n")
    with ContinuousBatchingEngine(
        scfg, world=args.tp, max_slots=args.batch, kv_pages=args.kv_pages,
        page_size=args.page_size, logits_mode=args.logits_mode,
        kv_dtype=args.kv_dtype, attn_backend=args.attn,
    ) as eng:
        print(explain_serve_plan(
            scfg.d_model, scfg.n_layers, scfg.vocab_size, P=args.tp,
            batch=args.batch, prompt_len=args.prompt_len,
            channels=(eng.channel,), logits_mode=args.logits_mode,
            flops_per_token=scfg.flops_per_token,
            kv_dtype=args.kv_dtype))


def _explain_fleet(cfg, args) -> None:
    from ..core.selector import explain_fleet_plan

    offered = args.offered_tps
    if offered is None:
        offered = args.rate_rps * args.max_new  # trace load in tokens/s
    print(f"fleet plan for {cfg.name} (full config, {args.channel} "
          f"channel, scale-up vs scale-out at the SLO):\n")
    print(explain_fleet_plan(
        cfg.d_model, cfg.n_layers, cfg.vocab_size,
        offered_tps=offered, slo_p99_ms=args.slo_p99_ms,
        batch=args.batch * 4, tokens_per_request=args.max_new,
        channels=(args.channel,), logits_mode=args.logits_mode,
    ))


def _fleet_trace(scfg, args):
    from ..serving.traffic import Trace, TrafficConfig, generate

    if args.trace:
        return Trace.load(args.trace).clipped(scfg.max_len)
    plen = max(1, args.prompt_len)
    return generate(TrafficConfig(
        seed=args.seed, pattern=args.traffic_pattern,
        rate_rps=args.rate_rps, duration_s=args.duration_s,
        burst=args.burst, period_s=args.period_s,
        vocab_size=scfg.vocab_size,
        prompt_mix=((max(1, plen // 2), plen, 1.0),),
        output_mix=((max(1, args.max_new // 2), args.max_new, 1.0),),
    ))


def _run_fleet(cfg, args) -> None:
    from ..serving.fleet import Autoscaler, FleetController

    scfg = _tp_config(cfg, args.prompt_len, args.max_new)
    trace = _fleet_trace(scfg, args)
    stats = trace.stats()
    print(f"trace: {stats['n_requests']} requests over "
          f"{stats['duration_s']}s virtual ({args.traffic_pattern}"
          f"{'' if args.trace is None else ' from ' + args.trace}, "
          f"peak {stats['peak_rate_rps']:.0f} rps)")
    autoscaler = None
    if args.autoscale:
        autoscaler = Autoscaler(slo_p99_ms=args.slo_p99_ms,
                                min_replicas=args.fleet,
                                max_replicas=args.max_replicas)
    kill = None
    if args.kill_replica is not None:
        kill = (args.kill_replica, args.kill_at_tick)
    t0 = time.perf_counter()
    with FleetController(
        scfg, n_replicas=args.fleet, tp=args.tp, max_slots=args.batch,
        kv_pages=args.kv_pages, page_size=args.page_size, seed=args.seed,
        logits_mode=args.logits_mode, kv_dtype=args.kv_dtype,
        attn_backend=args.attn, router=args.router,
        max_queue=args.max_queue, autoscaler=autoscaler,
        max_replicas=args.max_replicas, tick_s=args.tick_ms * 1e-3,
    ) as fleet:
        report = fleet.run_trace(trace, kill_replica_at=kill)
    dt = time.perf_counter() - t0
    for d in report.decisions:
        print(f"tick {d.tick}: {d.action} -> {d.replicas} replicas "
              f"(queue {d.queue_depth}, modeled p99 "
              f"{d.modeled_p99_ms:.1f}ms): {d.reason}")
    for h in report.history:
        print(f"membership commit gen {h['generation']}: dp={h['dp']} "
              f"({h.get('evidence', 'heal')}, re-routed {h['step']})")
    s = report.summary()
    print(f"fleet served {s['requests']} requests / {s['tokens']} tokens "
          f"in {s['ticks']} ticks ({report.tick_s*1e3:g}ms each): "
          f"{s['tok_per_vs']:.0f} tok/s virtual, p50 {s['p50_ms']:.2f}ms, "
          f"p99 {s['p99_ms']:.2f}ms, shed {s['shed']} "
          f"({100*s['shed_rate']:.1f}%), ${s['usd_per_mtok']:.4f}/1M tok, "
          f"{s['heals']} intra-replica heal(s), "
          f"{s['scale_events']} scale event(s) [{dt:.2f}s wall]")


def _run_continuous(cfg, args) -> None:
    scfg = _tp_config(cfg, args.prompt_len, args.max_new)
    rng = np.random.default_rng(args.seed)
    with ContinuousBatchingEngine(
        scfg, world=args.tp, max_slots=args.batch, kv_pages=args.kv_pages,
        page_size=args.page_size, seed=args.seed,
        logits_mode=args.logits_mode, kv_dtype=args.kv_dtype,
        attn_backend=args.attn,
    ) as eng:
        for _ in range(args.requests):
            plen = int(rng.integers(max(1, args.prompt_len // 2),
                                    args.prompt_len + 1))
            eng.submit(rng.integers(0, scfg.vocab_size, plen),
                       max_new=args.max_new)
        t0 = time.perf_counter()
        step = 0
        heals = 0
        while not eng.done:
            if args.kill_rank is not None and step == args.kill_at_step:
                print(f"step {step}: injecting failure of rank "
                      f"{args.kill_rank} (mid-collective)")
                eng.transport.kill(args.kill_rank, after_rounds=3)
            done, healed = eng.step_or_heal()
            if healed:
                heals += 1
                h = eng.controller.history[-1]
                print(f"healed: regrouped to world={h['dp']} "
                      f"(cancelled {h['cancelled']} in-flight, replayed "
                      f"{h['step']} sequences from the KV-page manifest)")
            if done:
                print(f"step {step}: finished {done} "
                      f"(active {len(eng.active)}, waiting "
                      f"{len(eng.waiting)}, "
                      f"pages {eng.kv.pages_in_use}/{eng.kv.n_pages})")
            step += 1
        dt = time.perf_counter() - t0
        toks = eng.tokens_emitted
        waits = sum(w for _, _, w in eng.comm_log)
        print(f"served {len(eng.finished)} requests / {toks} tokens in "
              f"{dt:.2f}s ({toks/dt:.1f} tok/s greedy, tp={eng.world} "
              f"sim ranks, {heals} heal(s), comm wait {waits*1e3:.1f}ms, "
              f"peak pages {eng.kv.peak_in_use}/{eng.kv.n_pages} "
              f"[{args.kv_dtype}: {eng.kv.peak_in_use*eng.kv.page_nbytes}"
              f" B/rank], attn={args.attn})")


def _run_wave(cfg, args) -> None:
    import jax

    from ..models import lm
    from ..serving.engine import ServeEngine

    rcfg = cfg.reduced()
    params = lm.init_params(rcfg, jax.random.key(0))
    eng = ServeEngine(rcfg, params, batch=args.batch,
                      max_len=args.prompt_len + args.max_new)
    rng = np.random.default_rng(args.seed)
    for _ in range(args.requests):
        eng.submit(rng.integers(0, rcfg.vocab_size, args.prompt_len))
    done, t0 = 0, time.perf_counter()
    while eng._queue:
        out = eng.run_wave(max_new=args.max_new)
        done += len(out)
        print(f"wave done: {len(out)} requests, sample output: {out[0][:8]}")
    dt = time.perf_counter() - t0
    toks = done * args.max_new
    print(f"served {done} requests / {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s greedy, reduced config on CPU)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch-policy", choices=["continuous", "wave"],
                    default="continuous")
    ap.add_argument("--tp", type=int, default=2,
                    help="tensor-parallel world size (continuous policy)")
    ap.add_argument("--batch", type=int, default=4,
                    help="max concurrent slots (continuous) / wave batch")
    ap.add_argument("--kv-pages", type=int, default=64,
                    help="KV page-pool size per rank shard")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--channel", default="ici",
                    help="channel the production --explain plan prices "
                         "(e.g. 'rdma' shows the lease-based one-sided "
                         "path winning the decode argmax regime)")
    ap.add_argument("--logits-mode", choices=["gather", "local-argmax"],
                    default="gather")
    ap.add_argument("--kv-dtype", choices=["f32", "bf16", "int8", "fp8"],
                    default="f32",
                    help="KV page storage tier (int8: 4x smaller pages, "
                    "per-(page, head) scales; emission wire follows)")
    ap.add_argument("--attn", choices=["gather", "kernel"],
                    default="gather",
                    help="decode attention backend: gather-and-pad numpy "
                    "path, or the Pallas paged-attention kernel reading "
                    "the page pool in place")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kill-rank", type=int, default=None,
                    help="inject a rank failure mid-decode (elastic demo)")
    ap.add_argument("--kill-at-step", type=int, default=2)
    ap.add_argument("--fleet", type=int, default=0,
                    help="run a FleetController over N engine replicas "
                    "replaying a seeded traffic trace (0: single engine)")
    ap.add_argument("--slo-p99-ms", type=float, default=50.0,
                    help="p99 latency SLO for the autoscaler and the "
                    "--explain fleet_plan table")
    ap.add_argument("--offered-tps", type=float, default=None,
                    help="offered load for --fleet --explain (default: "
                    "rate-rps * max-new tokens/s)")
    ap.add_argument("--router", choices=["least-loaded", "session-affine"],
                    default="least-loaded")
    ap.add_argument("--max-queue", type=int, default=8,
                    help="per-replica admission queue depth before shed")
    ap.add_argument("--autoscale", action="store_true",
                    help="enable SLO-driven scale-out/in between --fleet "
                    "and --max-replicas")
    ap.add_argument("--max-replicas", type=int, default=4)
    ap.add_argument("--trace", default=None,
                    help="replay a JSON traffic fixture instead of "
                    "generating one (see serving/traffic.py)")
    ap.add_argument("--traffic-pattern", choices=["poisson", "diurnal"],
                    default="poisson")
    ap.add_argument("--rate-rps", type=float, default=200.0)
    ap.add_argument("--duration-s", type=float, default=0.05)
    ap.add_argument("--burst", type=float, default=4.0,
                    help="diurnal peak/trough ratio")
    ap.add_argument("--period-s", type=float, default=0.02)
    ap.add_argument("--tick-ms", type=float, default=1.0,
                    help="virtual seconds per fleet tick")
    ap.add_argument("--kill-replica", type=int, default=None,
                    help="fail a whole replica mid-trace (fleet elastic "
                    "demo: its requests re-route, streams stay bit-exact)")
    ap.add_argument("--kill-at-tick", type=int, default=5)
    ap.add_argument("--explain", action="store_true",
                    help="print the serve_plan tables (prefill + decode) "
                    "and exit")
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny end-to-end smoke run (CI)")
    from .sanitize_cli import add_sanitize_args, arm, emit

    add_sanitize_args(ap)
    args = ap.parse_args()
    san = arm(args)  # before the engine builds its communicator

    cfg = configs.get(args.arch)
    if not cfg.supports_decode:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving")

    if args.explain:
        if args.fleet:
            _explain_fleet(cfg, args)
        else:
            _explain(cfg, args)
        return
    enable_compile_cache()
    if args.dry_run:
        args.requests = min(args.requests, 3)
        args.prompt_len = min(args.prompt_len, 4)
        args.max_new = min(args.max_new, 4)
        args.kv_pages = min(args.kv_pages, 16)
        args.rate_rps = min(args.rate_rps, 200.0)
        args.duration_s = min(args.duration_s, 0.02)
        if args.fleet:
            _run_fleet(cfg, args)
        else:
            _run_continuous(cfg, args)
        emit(san, args)
        print("dry-run ok")
        return
    if args.fleet:
        _run_fleet(cfg, args)
    elif args.batch_policy == "wave":
        _run_wave(cfg, args)
    else:
        _run_continuous(cfg, args)
    emit(san, args)


if __name__ == "__main__":
    main()
