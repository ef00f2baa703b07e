"""The training step names its layers, and the names cost nothing.

The fmi step carries ``jax.named_scope``s at its layer boundaries (``embed``,
``attention``, ``mlp``, ``flash_bwd``, ``head_loss``, ``optimizer`` and
``fmi/<op>/<algorithm>``), and the flash kernel is the ``pallas_call`` named
``flash_fwd``.  A profiler trace carries each op's ``op_name`` path, and the
benchmark's readers put device time to a layer by these names.  Scopes are
metadata: the compiled program with them stripped is the program without
them.

One subprocess on four virtual CPU devices builds the tiny step on one
device (no sync) and on four (ZeRO-1, FMI's reduce-scatter and allgather),
with the flash kernel in interpret mode, and compiles each twice: as it is,
and with ``jax.named_scope`` patched to do nothing.
"""

import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import contextlib, json, os, re
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from repro import configs
    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh
    from repro.models import lm
    from repro.training.train_step import TrainConfig, eval_opt_shapes, make_train_step

    ops._default_backend = lambda: "interpret"
    cfg = configs.get_reduced("yi-6b")
    METADATA = re.compile(r',? metadata=\\{(?:[^{}"]|"(?:[^"\\\\]|\\\\.)*")*\\}')

    def compiled(n):
        jax.clear_caches()
        mesh = make_host_mesh(n, 1)
        tcfg = TrainConfig(mode="fmi", zero1=n > 1)
        step, _, _ = make_train_step(cfg, tcfg, mesh)
        params = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.key(0)))
        opt = eval_opt_shapes(cfg, tcfg, mesh, False)
        tokens = jax.ShapeDtypeStruct((2 * n, 128), jnp.int32)
        with jax.set_mesh(mesh):
            return step.lower(params, opt, {"tokens": tokens, "labels": tokens}
                              ).compile().as_text()

    out = {}
    scoped = jax.named_scope
    for n in (1, 4):
        texts = []
        for scope in (scoped, lambda name: contextlib.nullcontext()):
            jax.named_scope = scope
            texts.append(compiled(n))  # one call site, so one source location
        txt, bare = texts
        out[n] = {"op_names": sorted(set(re.findall(r'op_name="([^"]*)"', txt))),
                  "stripped": METADATA.sub("", txt) == METADATA.sub("", bare),
                  "changed": txt != bare}
    print(json.dumps(out))
    """
)

LAYERS = ("embed", "attention", "mlp", "flash_fwd", "flash_bwd", "head_loss", "optimizer")
SYNC = ("fmi/reduce_scatter/recursive_halving", "fmi/allgather/recursive_doubling",
        "fmi/allreduce/recursive_doubling")


@pytest.fixture(scope="module")
def compiled():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    p = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True, text=True,
                       timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu",
                                         "PYTHONPATH": src})
    assert p.returncode == 0, p.stderr[-4000:]
    return {int(n): v for n, v in json.loads(p.stdout.strip().splitlines()[-1]).items()}


def _under(names, scope: str) -> bool:
    """Some op name stands under ``scope``, which a transformation may wrap
    (``jvp(head_loss)``)."""
    pat = re.compile(rf"(^|[/(]){re.escape(scope)}($|[/)])")
    return any(pat.search(n) for n in names)


@pytest.mark.parametrize("devices", [1, 4])
def test_step_names_its_layers(compiled, devices):
    names = compiled[devices]["op_names"]
    assert [s for s in LAYERS if not _under(names, s)] == []
    # the flash backward runs in the attention block, forward and remat alike
    assert _under([n for n in names if _under([n], "attention")], "flash_bwd")
    if devices == 1:  # one rank: the collectives return at once, with no ops
        assert not _under(names, "fmi")
    else:
        assert [s for s in SYNC if not _under(names, s)] == []
        # ZeRO-1's collectives run inside its optimizer
        assert _under([n for n in names if _under([n], "optimizer")], "fmi/reduce_scatter")


@pytest.mark.parametrize("devices", [1, 4])
def test_scopes_leave_the_program_unchanged(compiled, devices):
    """With tracing off a scope costs nothing: the compiled program differs
    from the unscoped one in its metadata alone."""
    assert compiled[devices]["changed"]
    assert compiled[devices]["stripped"]
