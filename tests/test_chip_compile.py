"""Every Pallas kernel compiles for a TPU v5e at real widths.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these run on the CPU.  Nothing executes: each
test lowers and compiles one kernel with ``interpret=False`` and checks
that the Mosaic kernel (``tpu_custom_call``) is in the compiled program.
This is what interpret-mode tests cannot see: TPU block-shape rules,
unsupported ops in a kernel body, and kernels GSPMD cannot partition.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers import
every test file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.quantize import (dequantize_blockwise, dequantize_page,
                                    quantize_blockwise, quantize_page)
from repro.kernels.ssm_scan import gla_scan
from repro.models import lm
from repro.models.attention import flash_attend
from repro.models.layers import Axes
from repro.training.train_step import TrainConfig, eval_opt_shapes, make_train_step


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the installed libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding) for shape, dtype in specs]


# llama3.2-1b: 32 q heads over 8 kv heads, head dim 64, bf16, 2048 tokens
FLASH = [((1, 32, 2048, 64), jnp.bfloat16), ((1, 8, 2048, 64), jnp.bfloat16),
         ((1, 8, 2048, 64), jnp.bfloat16)]


def test_flash_attention_forward_compiles(one_chip):
    txt = _compiled_text(flash_attention, *_shapes(one_chip, *FLASH))
    assert "tpu_custom_call" in txt


def test_flash_attention_gradient_compiles(one_chip):
    def loss(q, k, v):
        out = ops.flash_attention(q, k, v, backend="pallas")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    txt = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                         *_shapes(one_chip, *FLASH))
    assert "tpu_custom_call" in txt


def test_flash_attention_per_shard_under_four_chip_mesh(topo, monkeypatch):
    """xla-mode training: under a GSPMD mesh the kernel runs per shard."""
    monkeypatch.setattr(ops, "_default_backend", lambda: "pallas")
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    ax = Axes(data=("data", "model"), model=None, fsdp=(), sizes={"data": 4, "model": 1})
    shard = NamedSharding(mesh, P("data"))
    args = _shapes(shard, ((4, 2048, 32, 64), jnp.bfloat16),
                   ((4, 2048, 8, 64), jnp.bfloat16), ((4, 2048, 8, 64), jnp.bfloat16))
    with jax.set_mesh(mesh):
        txt = _compiled_text(lambda q, k, v: flash_attend(q, k, v, ax, causal=True), *args)
    assert "tpu_custom_call" in txt


def test_fmi_zero1_step_compiles_for_four_chips(topo, monkeypatch):
    """The fmi ZeRO-1 train step at llama3.2-1b widths (two of its layers):
    FMI collectives between the chips, the flash kernel inside.  Sharding
    by flat vectors instead of rows took the compiler minutes here."""
    monkeypatch.setattr(ops, "_default_backend", lambda: "pallas")
    cfg = dataclasses.replace(configs.get("llama3.2-1b"), n_layers=2)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    tcfg = TrainConfig(mode="fmi", zero1=True)
    rep = NamedSharding(mesh, P())

    def placed(tree, sharding):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)

    with jax.set_mesh(mesh):
        step, _, _ = make_train_step(cfg, tcfg, mesh)
        params = placed(jax.eval_shape(lambda: lm.init_params(cfg, jax.random.key(0))), rep)
        opt = placed(eval_opt_shapes(cfg, tcfg, mesh, False), rep)
        tokens = jax.ShapeDtypeStruct((4, 2048), jnp.int32,
                                      sharding=NamedSharding(mesh, P("data")))
        txt = step.lower(params, opt, {"tokens": tokens, "labels": tokens}).compile().as_text()
    assert "tpu_custom_call" in txt
    assert "collective-permute" in txt


# qwen3-1.7b: 16 q heads over 8 kv heads, head dim 128, 16-token pages
@pytest.mark.parametrize("pool", [jnp.float32, jnp.int8])
def test_paged_attention_compiles(one_chip, pool):
    n_pages, ps, hkv, d, B, hq, npm = 512, 16, 8, 128, 8, 16, 32
    args = _shapes(one_chip, ((B, hq, d), jnp.float32), ((n_pages, ps, hkv, d), pool),
                   ((n_pages, ps, hkv, d), pool), ((B, npm), jnp.int32), ((B,), jnp.int32),
                   ((n_pages, hkv), jnp.float32), ((n_pages, hkv), jnp.float32))

    def decode(q, kp, vp, table, lengths, ks, vs):
        return paged_attention(q, kp, vp, table, lengths, k_scale=ks, v_scale=vs)

    assert "tpu_custom_call" in _compiled_text(decode, *args)


def test_gla_scan_compiles(one_chip):
    """xlstm-125m mLSTM: 4 heads of width 192."""
    B, H, T, hd = 2, 4, 2048, 192
    args = _shapes(one_chip, ((B, H, T, hd), jnp.bfloat16), ((B, H, T, hd), jnp.bfloat16),
                   ((B, H, T, hd), jnp.bfloat16), ((B, H, T), jnp.float32),
                   ((B, H, T), jnp.float32))
    assert "tpu_custom_call" in _compiled_text(gla_scan, *args)


def test_blockwise_quantize_compiles(one_chip):
    (x,) = _shapes(one_chip, ((16, 1 << 20), jnp.float32))
    q, s = _shapes(one_chip, ((16, 1 << 20), jnp.int8), ((16, (1 << 20) // 256), jnp.float32))
    assert "tpu_custom_call" in _compiled_text(quantize_blockwise, x)
    assert "tpu_custom_call" in _compiled_text(dequantize_blockwise, q, s)


def test_page_quantize_compiles(one_chip):
    n_pages, ps, hkv, d = 512, 16, 8, 128
    (x,) = _shapes(one_chip, ((n_pages, ps, hkv, d), jnp.float32))
    q, s = _shapes(one_chip, ((n_pages, ps, hkv, d), jnp.int8), ((n_pages, hkv), jnp.float32))
    assert "tpu_custom_call" in _compiled_text(quantize_page, x)
    assert "tpu_custom_call" in _compiled_text(dequantize_page, q, s)
