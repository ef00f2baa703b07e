import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    # all-reduce-promotion is an XLA:CPU numerics pass that segfaults on
    # some large partitioned modules (CloneAllReduce on a copy-reducer);
    # it is irrelevant for compile-only dry-runs (nothing executes)
    "--xla_disable_hlo_passes=all-reduce-promotion"
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script:

  1. builds the production mesh (16x16 single pod / 2x16x16 multi-pod),
  2. builds the jitted train or serve step with full shardings,
  3. ``.lower(**ShapeDtypeStruct stand-ins).compile()`` — no allocation,
  4. records ``memory_analysis()`` (fits-in-HBM proof), ``cost_analysis()``
     (FLOPs/bytes), and the collective schedule parsed from the optimized
     HLO (op kind, local bytes, wire bytes, group size, ICI vs DCN),
  5. derives the three roofline terms (EXPERIMENTS.md §Roofline) and dumps
     one JSON artifact per cell under benchmarks/artifacts/dryrun/.

Usage:
    python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    python -m repro.launch.dryrun --arch yi-6b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all            # every runnable cell
    python -m repro.launch.dryrun --arch ... --explain   # selector table
"""

import argparse
import json
import math
import re
import sys

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .. import configs
from ..core.models import V5E
from ..models import lm
from ..models.config import ModelConfig
from ..models.layers import Axes
from ..serving.engine import ServeConfig, make_serve_fns
from ..training.train_step import TrainConfig, make_train_step
from .mesh import make_production_mesh, mesh_shape_dict

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "artifacts", "dryrun")

# per-arch gradient-accumulation defaults (fit 16 GiB/chip at train_4k)
MICROBATCHES = {
    "llama-3.2-vision-90b": 2,
    "deepseek-v2-236b": 1,
    "llama4-maverick-400b-a17b": 2,
    "granite-3-8b": 2,
    "yi-6b": 2,
    "llama3.2-1b": 2,
    "qwen3-1.7b": 2,
    "hubert-xlarge": 2,
}
# moment dtype: bf16 where f32 m/v would blow the 16 GiB budget
STATE_DTYPE = {
    "llama4-maverick-400b-a17b": "bfloat16",
    "deepseek-v2-236b": "bfloat16",
    "llama-3.2-vision-90b": "bfloat16",
}

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0,
}

_COLL_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(s: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(s):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_info(line: str, pod_size: int) -> tuple[int, bool]:
    """(group size, crosses pod boundary) from replica_groups annotation."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[(\d+)\]", line)
    if m:  # iota form: [ngroups, gsize]<=[N] (+ optional transpose dims)
        ngroups, gsize, n = int(m.group(1)), int(m.group(2)), int(m.group(3))
        # iota groups are contiguous unless a transpose reorders them
        tm = re.search(r"<=\[(\d+(?:,\d+)*)\]T\(([\d,]+)\)", line)
        crosses = gsize > pod_size if not tm else _iota_crosses(tm, pod_size)
        return gsize, crosses
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        ids = [int(x) for x in m.group(1).split(",") if x.strip()]
        crosses = (min(ids) // pod_size) != (max(ids) // pod_size) if ids else False
        return len(ids), crosses
    return 0, False


def _iota_crosses(tm, pod_size: int) -> bool:
    dims = [int(x) for x in tm.group(1).split(",")]
    # group stride spans the full device space if the leading (pod) dim is
    # inside one group after transpose; conservative: crossing if product of
    # grouped dims exceeds pod_size
    return math.prod(dims) > pod_size


WIRE_FACTOR = {
    # wire bytes per chip as a multiple of the op's *result* local bytes
    "all-reduce": 2.0,  # ring: reduce-scatter + allgather phases
    "all-gather": 1.0,  # receives result minus own shard
    "reduce-scatter": 1.0,  # sends input minus own shard ~= result * (P-1)
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _split_computations(hlo: str) -> dict[str, list[str]]:
    """HLO text -> {computation name: lines}.  Computations start at column 0
    with a '{'-terminated header; instructions are indented."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)", line)
            cur = m.group(1) if m else None
            if cur:
                comps[cur] = []
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    return comps


def _loop_multipliers(comps: dict[str, list[str]]) -> dict[str, float]:
    """Execution-count multiplier per computation: while bodies execute
    trip-count times (trip recovered from the loop condition's compare
    constant).  XLA cost analysis misses this; we do not."""
    parent_of: dict[str, tuple[str, str]] = {}  # body -> (parent, cond)
    for name, lines in comps.items():
        for line in lines:
            if " while(" not in line:
                continue
            bm = re.search(r"body=%?([\w\.\-]+)", line)
            cm = re.search(r"condition=%?([\w\.\-]+)", line)
            if bm:
                parent_of[bm.group(1)] = (name, cm.group(1) if cm else "")

    def trip(cond_name: str) -> int:
        consts = []
        for line in comps.get(cond_name, []):
            for c in re.findall(r"constant\((\d+)\)", line):
                v = int(c)
                if 1 < v <= 10**6:
                    consts.append(v)
        return max(consts) if consts else 1

    mult: dict[str, float] = {}

    def resolve(name: str) -> float:
        if name in mult:
            return mult[name]
        if name not in parent_of:
            mult[name] = 1.0
            return 1.0
        parent, cond = parent_of[name]
        m = resolve(parent) * trip(cond)
        mult[name] = m
        return m

    for name in comps:
        resolve(name)
    # called (non-while) computations inherit their caller's multiplier via
    # calls/fusions; approximate by max caller multiplier
    for name, lines in comps.items():
        for line in lines:
            for callee in re.findall(r"(?:calls=|to_apply=)%?([\w\.\-]+)", line):
                if callee in mult and mult[callee] < mult.get(name, 1.0):
                    mult[callee] = mult[name]
    return mult


def parse_collectives(hlo: str, pod_size: int = 256):
    """Collective schedule from post-SPMD HLO (local shapes), with while-loop
    execution multipliers applied (a collective inside the layer scan counts
    n_groups times, inside grad-accum x microbatches, etc.)."""
    comps = _split_computations(hlo)
    mults = _loop_multipliers(comps)
    out = []
    for cname, lines in comps.items():
        mult = mults.get(cname, 1.0)
        for line in lines:
            m = _COLL_RE.search(line)
            if not m:
                continue
            if "-done(" in line or "-done " in line:
                continue  # async pair: count the -start only
            shape_s, op = m.group(1), m.group(2)
            nbytes = _shape_bytes(shape_s)
            gsize, crosses = _group_info(line, pod_size)
            wire = nbytes * WIRE_FACTOR[op]
            if op == "reduce-scatter" and gsize:
                wire = nbytes * (gsize - 1)  # result is the scattered shard
            out.append(
                dict(op=op, local_bytes=nbytes, wire_bytes=wire * mult,
                     wire_bytes_once=wire, executions=mult,
                     group_size=gsize, channel="dcn" if crosses else "ici")
            )
    return out


def model_flops(cfg: ModelConfig, kind: str, batch: int, seq: int) -> float:
    """MODEL_FLOPS = 6·N_active·tokens (train) / 2·N_active·tokens (fwd)."""
    n_active = lm.count_params(cfg, active_only=True)
    tokens = batch * seq if kind != "decode" else batch  # decode: 1 tok/slot
    mult = 6 if kind == "train" else 2
    return float(mult * n_active * tokens)


def roofline_terms(flops_per_chip: float, hbm_bytes: float, colls: list, chips: int):
    compute_s = flops_per_chip / V5E.peak_flops_bf16
    memory_s = hbm_bytes / V5E.hbm_bw
    ici = sum(c["wire_bytes"] for c in colls if c["channel"] == "ici")
    dcn = sum(c["wire_bytes"] for c in colls if c["channel"] == "dcn")
    coll_s = ici / (V5E.ici_bw * V5E.ici_links) + dcn / V5E.dcn_bw
    return dict(
        compute_s=compute_s, memory_s=memory_s, collective_s=coll_s,
        ici_wire_bytes=ici, dcn_wire_bytes=dcn,
    )


def build_step(cfg: ModelConfig, shape_name: str, mesh, multi_pod: bool,
               mode: str, microbatches: int | None = None):
    """Returns (jitted_fn, arg ShapeDtypeStructs tuple)."""
    import dataclasses

    shp = configs.SHAPES[shape_name]
    B, S = shp["global_batch"], shp["seq_len"]
    msh = mesh_shape_dict(mesh)
    data_deg = msh.get("data", 1) * msh.get("pod", 1)

    if shp["kind"] == "train":
        mb = microbatches or MICROBATCHES.get(cfg.name, 1)
        from ..optim.optimizer import OptConfig

        opt = OptConfig(state_dtype=STATE_DTYPE.get(cfg.name, "float32"))
        if mode == "fmi":
            # paper-technique production defaults: explicit ZeRO-1 over the
            # data axes; hierarchical ICI/DCN reduction across pods
            tcfg = TrainConfig(mode=mode, microbatches=mb, optimizer=opt,
                               zero1=not multi_pod, hierarchical=multi_pod,
                               allreduce="ring")
        else:
            tcfg = TrainConfig(mode=mode, microbatches=mb, optimizer=opt)
        step, ax, pspecs = make_train_step(cfg, tcfg, mesh, multi_pod, global_batch=B)
        pshapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.key(0)))
        from ..training.train_step import eval_opt_shapes

        oshapes = eval_opt_shapes(cfg, tcfg, mesh, multi_pod, global_batch=B)
        bshapes = lm.input_specs(cfg, B, S)
        return step, (pshapes, oshapes, bshapes)

    # serving cells
    scfg = ServeConfig(batch=B, max_len=S)
    prefill_jit, decode_jit, ax, sh = make_serve_fns(cfg, scfg, mesh, multi_pod)
    pshapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.key(0)))
    if shp["kind"] == "prefill":
        bshapes = lm.input_specs(cfg, B, S)
        bshapes.pop("labels", None)
        if not cfg.supports_decode:  # encoder: cacheless forward
            return prefill_jit, (pshapes, bshapes)
        cshapes = jax.eval_shape(lambda: lm.init_cache(cfg, B, S))
        return prefill_jit, (pshapes, bshapes, cshapes)
    # decode: one new token against an S-long cache
    cshapes = jax.eval_shape(lambda: lm.init_cache(cfg, B, S))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return decode_jit, (pshapes, tok, pos, cshapes)


def run_cell(arch: str, shape_name: str, multi_pod: bool, mode: str = "xla",
             microbatches: int | None = None, save: bool = True,
             hlo_out: str | None = None) -> dict:
    cfg = configs.get(arch)
    status = configs.cell_status(cfg, shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell_id = f"{configs.canonical(arch)}__{shape_name}__{mesh_name}__{mode}"
    if status != "run":
        rec = dict(cell=cell_id, arch=cfg.name, shape=shape_name, mesh=mesh_name,
                   mode=mode, status=status)
        if save:
            _save(rec, cell_id)
        print(f"[{cell_id}] {status}")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(math.prod(mesh.devices.shape))
    shp = configs.SHAPES[shape_name]

    with jax.set_mesh(mesh):
        step, args = build_step(cfg, shape_name, mesh, multi_pod, mode, microbatches)
        lowered = step.lower(*args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    if hlo_out:
        with open(hlo_out, "w") as f:
            f.write(hlo)
    colls = parse_collectives(hlo, pod_size=256)
    msh = mesh_shape_dict(mesh)
    data_deg = msh.get("data", 1) * msh.get("pod", 1)
    mb = microbatches or MICROBATCHES.get(cfg.name, 1)

    from .analysis import analytic_memory_gib, cell_cost
    from .policy import plan as _plan

    pol = _plan(cfg, mesh, multi_pod, shp["kind"], shp["global_batch"])
    seq_shard = msh.get(pol.seq, 1) if pol.seq else 1
    sdb = 2 if cfg.name in STATE_DTYPE else 4
    amem = analytic_memory_gib(
        cfg, shp["kind"], shp["global_batch"], shp["seq_len"], chips,
        microbatches=mb, data_degree=data_deg, state_dtype_bytes=sdb,
        seq_shard=seq_shard,
    )
    ac = cell_cost(
        cfg, shp["kind"], shp["global_batch"], shp["seq_len"], chips,
        microbatches=mb, data_degree=data_deg,
        state_dtype_bytes=sdb,
    )
    flops = ac.flops_global / chips  # true executed FLOPs per chip
    hbm_bytes = ac.hbm_bytes_per_chip
    terms = roofline_terms(flops, hbm_bytes, colls, chips)
    mflops = model_flops(cfg, shp["kind"], shp["global_batch"], shp["seq_len"])
    per_chip_model = mflops / chips
    # compiled cost_analysis recorded verbatim (NB: while/scan bodies are
    # counted ONCE by XLA regardless of trip count — see EXPERIMENTS.md)
    xla_raw = dict(
        flops=float(cost.get("flops", 0.0)),
        bytes_accessed=float(cost.get("bytes accessed", 0.0)),
    )

    from collections import Counter

    coll_summary = Counter()
    coll_bytes = Counter()
    for c in colls:
        key = f"{c['op']}@{c['channel']}"
        coll_summary[key] += 1
        coll_bytes[key] += c["wire_bytes"]

    terms_order = sorted(
        [("compute", terms["compute_s"]), ("memory", terms["memory_s"]),
         ("collective", terms["collective_s"])], key=lambda t: -t[1]
    )
    rec = dict(
        cell=cell_id, arch=cfg.name, shape=shape_name, mesh=mesh_name, mode=mode,
        status="ok", chips=chips,
        memory=dict(
            argument_gib=mem.argument_size_in_bytes / 2**30,
            output_gib=mem.output_size_in_bytes / 2**30,
            temp_gib=mem.temp_size_in_bytes / 2**30,
            alias_gib=mem.alias_size_in_bytes / 2**30,
            # NB: XLA:CPU widens bf16 buffers to f32 (verified; see
            # EXPERIMENTS.md §Dry-run caveats) — peak_gib_cpu is an upper
            # bound ~2x above the TPU target for bf16-heavy cells.
            peak_gib_cpu=(mem.argument_size_in_bytes + mem.output_size_in_bytes
                          + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2**30,
            analytic=amem,
            fits=amem["total_gib"] < V5E.hbm_gib,
        ),
        flops_per_chip=flops,
        hbm_bytes_per_chip=hbm_bytes,
        flops_components=ac.flops_components,
        bytes_components=ac.bytes_components,
        xla_cost_raw=xla_raw,
        model_flops_global=mflops,
        model_flops_per_chip=per_chip_model,
        useful_flops_ratio=(per_chip_model / flops) if flops else None,
        terms=terms,
        dominant=terms_order[0][0],
        collective_counts=dict(coll_summary),
        collective_wire_bytes=dict(coll_bytes),
        n_collectives=len(colls),
    )
    if save:
        _save(rec, cell_id)
    peak = rec["memory"]["analytic"]["total_gib"]
    print(
        f"[{cell_id}] ok: ~{peak:.2f} GiB/chip target "
        f"(cpu {rec['memory']['peak_gib_cpu']:.1f}, fits={rec['memory']['fits']}), "
        f"flops/chip {flops:.3e}, terms: c={terms['compute_s']*1e3:.2f}ms "
        f"m={terms['memory_s']*1e3:.2f}ms coll={terms['collective_s']*1e3:.2f}ms "
        f"-> {rec['dominant']}-bound, useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'],3)}"
    )
    return rec


def _save(rec: dict, cell_id: str):
    os.makedirs(ART_DIR, exist_ok=True)
    with open(os.path.join(ART_DIR, cell_id + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str)
    ap.add_argument("--shape", type=str, choices=list(configs.SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mode", type=str, default="xla", choices=["xla", "fmi"])
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--grid", action="store_true",
                    help="all shapes x both meshes for --arch")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--hlo-out", type=str, default=None)
    ap.add_argument("--explain", action="store_true",
                    help="print the FMI selector table for this cell's grad sync")
    ap.add_argument("--channel", type=str, default=None,
                    help="add one channel (e.g. rdma) to the --explain "
                         "candidate set, ahead of the built-in table")
    args = ap.parse_args()

    if args.explain and args.arch:
        from ..core.selector import explain, explain_bucket_plan, select

        cfg = configs.get(args.arch)
        nbytes = lm.count_params(cfg) * 2 / 256  # bf16 grads per chip share
        # full registry table: direct ici, provider xla, mediated host, sim
        # oracle, one-sided rdma — plus two-level hierarchical composites
        chans = ("ici", "xla", "host", "sim", "rdma")
        if args.channel and args.channel not in chans:
            chans = (args.channel,) + chans
        print(f"grad-sync allreduce, {nbytes/1e6:.1f} MB/chip, 16 ranks:\n")
        # flow=True adds the modeled-vs-flow divergence column: every flat
        # candidate re-run on the flow-level backend (emergent link
        # contention over the channel's implied topology; docs/flowsim.md)
        print(explain("allreduce", nbytes, 16, channels=chans, flow=True))
        best = select("allreduce", nbytes, 16, channels=chans)
        print(f"\nselected: {best.channel}/{best.algorithm} depth={best.depth} "
              f"({best.time_s*1e6:.1f}us, ${best.price_usd:.3e})")
        # calibration: fit per-channel corrections against the flow backend
        # on a quick sweep; selector.select/bucket_plan accept the result
        # via calibration= to re-rank with corrected predictions
        from ..core.selector import calibrate, explain_calibration

        # cap the sweep at 4 MiB: expand_collective runs real stacked
        # payloads, so P=16 points at the full 13 MB grad share cost
        # minutes of array copies without changing the fitted scales
        cal = calibrate(channels=("sim", "host"), P_values=(8, 16),
                        nbytes_grid=(1 << 16, 1 << 20,
                                     min(int(nbytes), 1 << 22)))
        print(f"\n{explain_calibration(cal)}")
        cbest = select("allreduce", nbytes, 16, channels=chans,
                       calibration=cal)
        print(f"calibrated pick: {cbest.channel}/{cbest.algorithm} "
              f"depth={cbest.depth} ({cbest.time_s*1e6:.1f}us corrected)")
        # one-sided rdma regime: the grad sync above is bandwidth-bound, so
        # the lease channel loses it — the latency-bound end of the software
        # stack (the serving decode argmax exchange, 8 B/rank) is where the
        # near-α-only hops=1 path wins.  Show the pick and the modeled
        # handover point to the two-sided broker (docs/rdma.md).
        from ..core.selector import crossover_nbytes

        argmax_bytes = 16 * 2 * 4  # 16 ranks x (max, argmax) f32 pair
        small = select("allgather", argmax_bytes, 16,
                       channels=("rdma", "host", "sim"))
        xb = crossover_nbytes("allreduce", 16, "rdma", "host")
        print(f"\nrdma (lease-based one-sided) regime: decode-argmax "
              f"allgather {argmax_bytes} B -> {small.channel}/"
              f"{small.algorithm} ({small.time_s*1e6:.2f}us); handover to "
              f"host broker at ~{xb/1e3:.0f} KB (allreduce envelope, 16 ranks)")
        # bucketed-overlap plan: how the CommScheduler would coalesce the
        # per-layer gradient requests, with the backward compute window the
        # roofline model predicts for this arch as the overlap budget
        from ..core.models import V5E

        shp = configs.SHAPES[args.shape] if args.shape else {"kind": "train",
                                                             "global_batch": 256,
                                                             "seq_len": 4096}
        mfl = model_flops(cfg, "train", shp["global_batch"], shp["seq_len"])
        # backward ≈ 2/3 of the 6·N·tokens train FLOPs, spread over 256 chips
        backward_s = (2 / 3) * mfl / 256 / V5E.peak_flops_bf16
        print(f"\n{explain_bucket_plan('allreduce', nbytes, 16, channels=('ici',), compute_s=backward_s)}")
        # elastic rescale plan: one of the 16 ranks just died — continue
        # degraded (backup buddies + stretched collectives) or pay the
        # restart (reform + reshard the checkpoint + redo the steps since
        # the last commit) to regroup at 15/8 ranks now?
        from ..core.selector import explain_rescale_plan

        step_s = mfl / 256 / V5E.peak_flops_bf16  # full fwd+bwd compute
        ckpt_bytes = lm.count_params(cfg) * (2 + 8)  # bf16 params + f32 m/v
        print(f"\n{explain_rescale_plan(nbytes, 16, 15, steps_remaining=1000, compute_s=step_s, channels=('ici',), ckpt_bytes=ckpt_bytes, steps_since_ckpt=25)}")
        print("\ncheckers: comm-lint FMI001-FMI006 (python tools/comm_lint.py"
              " src/repro --strict) | CommSanitizer (FMI_SANITIZE=1 or "
              "--sanitize on train/serve) — see docs/analysis.md")
        return

    if args.all or args.grid:
        ok, fail = 0, 0
        archs = [configs.canonical(args.arch)] if args.grid else configs.ARCH_IDS
        for arch in archs:
            for shape in configs.SHAPES:
                for mp in (False, True):
                    mesh_name = "2x16x16" if mp else "16x16"
                    cell_id = f"{arch}__{shape}__{mesh_name}__{args.mode}"
                    path = os.path.join(ART_DIR, cell_id + ".json")
                    if args.skip_existing and os.path.exists(path):
                        continue
                    try:
                        rec = run_cell(arch, shape, mp, args.mode)
                        ok += rec.get("status") == "ok"
                    except Exception as e:  # noqa: BLE001
                        fail += 1
                        print(f"[{cell_id}] FAILED: {type(e).__name__}: {e}",
                              file=sys.stderr)
        print(f"dry-run complete: {ok} compiled, {fail} failed")
        sys.exit(1 if fail else 0)

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    run_cell(args.arch, args.shape, args.multi_pod, args.mode,
             args.microbatches, hlo_out=args.hlo_out)


if __name__ == "__main__":
    main()
