"""Multi-device integration tests (8 simulated CPU devices via subprocess —
the main pytest process keeps its single real device, per the harness
contract).  One subprocess runs a battery of distributed assertions:

  * shard_map FMI collectives == jax.lax references on a real mesh
  * fmi-mode train step == xla-mode train step (same data, same update)
  * ZeRO-1 == replicated AdamW (parameter parity after steps)
  * compressed allreduce trains (loss decreases)
  * elastic rescale: train on dp=4, fail to dp=2, restore + resume
"""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, PartitionSpec as P

    from repro import configs
    from repro.core import collectives as C
    from repro.core.communicator import Communicator
    from repro.data.pipeline import DataConfig, synthetic_batch
    from repro.models import lm
    from repro.optim.optimizer import OptConfig
    from repro.training.train_step import TrainConfig, init_opt_state, make_train_step, place_state

    failures = []

    def check(name, ok, detail=""):
        print(("PASS " if ok else "FAIL ") + name + (" " + detail if detail else ""))
        if not ok:
            failures.append(name)

    # ---- 1. shard_map collectives vs lax references --------------------
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    comm = Communicator(axes=("data",), sizes=(8,))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)

    def run(fn, out_specs=P("data", None)):
        g = jax.shard_map(lambda v: fn(v[0])[None], mesh=mesh,
                             in_specs=P("data", None), out_specs=out_specs,
                             axis_names={"data"})
        with jax.set_mesh(mesh):
            return np.asarray(jax.jit(g)(x))

    for algo in ("ring", "rabenseifner", "recursive_doubling", "xla"):
        got = run(lambda v, a=algo: C.allreduce(v, comm, algorithm=a))
        check(f"allreduce/{algo}", np.allclose(got, x.sum(0), atol=1e-4))

    got = run(lambda v: C.reduce_scatter(v, comm, algorithm="recursive_halving"))
    check("reduce_scatter", np.allclose(got, x.sum(0).reshape(8, 2), atol=1e-4))

    # row blocks (ZeRO-1's layout): split and stacked along the leading axis
    for algo in ("recursive_halving", "ring", "xla"):
        got = run(lambda v, a=algo: C.reduce_scatter(v.reshape(16, 1), comm,
                                                     algorithm=a, rows=True))
        check(f"reduce_scatter rows/{algo}",
              np.allclose(got, x.sum(0).reshape(8, 2, 1), atol=1e-4))
    for algo in ("recursive_doubling", "ring", "xla"):
        got = run(lambda v, a=algo: C.allgather(v.reshape(2, 8), comm,
                                                algorithm=a, rows=True))
        check(f"allgather rows/{algo}",
              all(np.array_equal(g, np.asarray(x).reshape(16, 8)) for g in got))

    got = run(lambda v: C.scan(v, comm))
    check("scan", np.allclose(got, np.cumsum(x, 0), atol=1e-4))

    # nonblocking request layer on the mesh transport: iallreduce == allreduce,
    # and the bucketed scheduler path is bit-exact with the blocking path for
    # a rank-order-independent algorithm
    got = run(lambda v: C.allreduce(v, comm, algorithm="recursive_doubling"))
    got_i = run(lambda v: comm.iallreduce(v, algorithm="recursive_doubling").wait())
    check("iallreduce==allreduce", np.array_equal(got, got_i))

    tree = {f"w{i}": x[:, i * 2:(i + 1) * 2] for i in range(8)}
    def sync(schedule, **kw):
        def body(v):
            tr = {k: t[0] for k, t in v.items()}
            out = C.allreduce_tree(tr, comm, algorithm="recursive_doubling",
                                   mean=True, schedule=schedule, **kw)
            return {k: t[None] for k, t in out.items()}
        g = jax.shard_map(body, mesh=mesh,
                             in_specs=({k: P("data", None) for k in tree},),
                             out_specs={k: P("data", None) for k in tree},
                             axis_names={"data"})
        with jax.set_mesh(mesh):
            return jax.tree.map(np.asarray, jax.jit(g)(tree))
    blk = sync("blocking")
    bkt = sync("bucketed", bucket_bytes=16)  # tiny buckets: every leaf its own
    check("bucketed==blocking mesh", all(
        np.array_equal(blk[k], bkt[k]) for k in tree))

    # ---- 2. fmi-mode vs xla-mode training parity -----------------------
    TINY = configs.get_reduced("llama3_2_1b", n_layers=2, d_model=64, n_heads=4,
                               n_kv_heads=2, d_ff=128, vocab_size=256, head_dim=16)
    mesh2 = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    opt = OptConfig(lr=1e-3, warmup_steps=0, total_steps=10, clip_norm=0.0)
    dcfg = DataConfig()

    def train(tcfg, steps=3):
        step_fn, axx, pspecs = make_train_step(TINY, tcfg, mesh2, False)
        with jax.set_mesh(mesh2):
            params = lm.init_params(TINY, jax.random.key(0))
            if tcfg.zero1 and tcfg.mode == "fmi":
                from repro.training import zero1 as z1
                from repro.launch.policy import plan
                pol = plan(TINY, mesh2, False, "train")
                comm = Communicator(axes=pol.data,
                                    sizes=tuple({"data":4,"model":2}[a] for a in pol.data))
                layout = z1.make_layout(params, comm.size)
                opt_state = z1.zero1_init(params, layout, comm, "float32")
            else:
                opt_state = init_opt_state(TINY, tcfg, params)
            if not (tcfg.zero1 and tcfg.mode == "fmi"):
                params, opt_state = place_state(mesh2, params, opt_state, pspecs, tcfg)
            losses = []
            for s in range(steps):
                b = jax.tree.map(jnp.asarray, synthetic_batch(dcfg, TINY, 8, 32, s))
                params, opt_state, m = step_fn(params, opt_state, b)
                losses.append(float(m["loss"]))
        return losses, params

    l_xla, p_xla = train(TrainConfig(mode="xla", optimizer=opt, donate=False))
    l_fmi, p_fmi = train(TrainConfig(mode="fmi", optimizer=opt, donate=False,
                                     allreduce="ring"))
    dl = max(abs(a - b) for a, b in zip(l_xla, l_fmi))
    check("fmi==xla losses", dl < 5e-3, f"dloss={dl:.2e}")
    dp = max(float(jnp.abs(a - b).max()) for a, b in
             zip(jax.tree.leaves(p_xla), jax.tree.leaves(p_fmi)))
    check("fmi==xla params", dp < 5e-3, f"dparam={dp:.2e}")

    l_rd, p_rd = train(TrainConfig(mode="fmi", optimizer=opt, donate=False,
                                   allreduce="recursive_doubling"))
    check("fmi rd==ring", max(abs(a-b) for a,b in zip(l_fmi, l_rd)) < 1e-4)

    # bucketed overlap schedule: per-layer requests coalesced by the
    # CommScheduler must train bit-identically to the blocking fused sync
    # (recursive doubling reduces every element in the same rank order
    # regardless of which bucket it travels in)
    l_bk, p_bk = train(TrainConfig(mode="fmi", optimizer=opt, donate=False,
                                   allreduce="recursive_doubling",
                                   schedule="bucketed", bucket_mb=0.01))
    dbk = max(float(jnp.abs(a - b).max()) for a, b in
              zip(jax.tree.leaves(p_rd), jax.tree.leaves(p_bk)))
    check("bucketed==blocking train", dbk == 0.0, f"dparam={dbk:.2e}")

    # ---- 3. ZeRO-1 parity ----------------------------------------------
    l_z1, p_z1 = train(TrainConfig(mode="fmi", optimizer=opt, donate=False,
                                   zero1=True))
    dz = max(abs(a - b) for a, b in zip(l_xla, l_z1))
    check("zero1 losses match", dz < 5e-3, f"dloss={dz:.2e}")
    dzp = max(float(jnp.abs(a - b).max()) for a, b in
              zip(jax.tree.leaves(p_xla), jax.tree.leaves(p_z1)))
    check("zero1 params match", dzp < 5e-3, f"dparam={dzp:.2e}")

    # ---- 4. compressed allreduce trains ---------------------------------
    l_c, _ = train(TrainConfig(mode="fmi", optimizer=opt, donate=False,
                               compression="int8"), steps=6)
    check("int8 compressed trains", l_c[-1] < l_c[0] + 0.05 and np.isfinite(l_c).all(),
          f"{l_c[0]:.3f}->{l_c[-1]:.3f}")

    # ---- 5. elastic rescale 4 -> 2 data ranks --------------------------
    import tempfile
    from repro.checkpoint import CheckpointManager
    from repro.launch.mesh import make_host_mesh

    tmp = tempfile.mkdtemp()
    tcfg = TrainConfig(mode="xla", optimizer=opt, donate=False)
    mesh4 = jax.make_mesh((4, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2,
                             devices=jax.devices()[:4])
    step4, _, pspecs4 = make_train_step(TINY, tcfg, mesh4, False)
    with jax.set_mesh(mesh4):
        params = lm.init_params(TINY, jax.random.key(0))
        opt_state = init_opt_state(TINY, tcfg, params)
        params, opt_state = place_state(mesh4, params, opt_state, pspecs4, tcfg)
        for s in range(2):
            b = jax.tree.map(jnp.asarray, synthetic_batch(dcfg, TINY, 8, 32, s))
            params, opt_state, m = step4(params, opt_state, b)
        mgr = CheckpointManager(tmp)
        mgr.save_async({"params": params, "opt": opt_state}, 2)
        mgr.wait()
        loss_before = float(m["loss"])

    # "failure": rebuild on 2 surviving devices, restore, continue
    mesh2d = jax.make_mesh((2, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2,
                              devices=jax.devices()[:2])
    step2, _, pspecs2 = make_train_step(TINY, tcfg, mesh2d, False)
    with jax.set_mesh(mesh2d):
        shapes = jax.eval_shape(lambda: {"params": params, "opt": opt_state})
        state, step = mgr.restore_latest(shapes)
        ok_resume = step == 2
        # elastic resharding: the restored host arrays are placed onto the
        # NEW (smaller) mesh's shardings
        p2, o2 = place_state(mesh2d, state["params"], state["opt"], pspecs2, tcfg)
        for s in range(2, 4):
            b = jax.tree.map(jnp.asarray, synthetic_batch(dcfg, TINY, 8, 32, s))
            p2, o2, m2 = step2(p2, o2, b)
        check("elastic resume trains", ok_resume and np.isfinite(float(m2["loss"])),
              f"loss={float(m2['loss']):.3f} (pre-failure {loss_before:.3f})")

    print("ALL_DONE failures=" + str(len(failures)))
    """
)


@pytest.mark.timeout(1200)
def test_multidevice_battery():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, env=env,
        timeout=1100,
    )
    print(r.stdout)
    if r.returncode != 0:
        print(r.stderr[-4000:])
    assert r.returncode == 0, "multidevice subprocess crashed"
    assert "ALL_DONE failures=0" in r.stdout, r.stdout
