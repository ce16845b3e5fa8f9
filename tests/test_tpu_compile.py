"""The served path's Pallas kernels, compiled for a described TPU v5e chip.

Interpret mode runs a kernel's body in XLA and accepts block shapes that
Mosaic refuses (blocks off the (8, 128) tiling, scalar stores to VMEM), so
the interpret-mode parity tests cannot show that a kernel compiles for
the chip.  These tests compile each kernel entry point with
``interpret=False`` for one chip of a described ``v5e:2x2`` topology — no
chip is attached, nothing runs — at the shapes the serving engine
launches, with the launch parameters the TPU tuning table resolves, and
require the Mosaic kernel (``tpu_custom_call``) in the compiled program.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
The tests skip only where no TPU compiler is installed; any other failure
to describe the chip fails them.
"""
import importlib.util
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, tuning
from repro.kernels.elementwise import (LANES, ddim_fused_pallas,
                                       parareal_update_pallas,
                                       parareal_update_residual_pallas)

# one 32x32x3 f32 sample is 24 rows of 128 lanes; the engine's 4 slots
SAMPLE_ROWS = 32 * 32 * 3 // LANES
LANES_K = 4


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) in this installation")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    mp = pytest.MonkeyPatch()
    # libtpu would otherwise write its logs outside the checkout
    mp.setenv("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off here
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # other test modules turn on 64-bit mode as they are imported; the
    # served path runs 32-bit, and Mosaic does not lower 64-bit indices
    was_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_x64", was_x64)
        jax.config.update("jax_enable_compilation_cache", was_on)
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _tpu_tile_rows() -> int:
    cfg = tuning.get_tuner().resolve("elementwise", backend="tpu",
                                     dtype=jnp.float32)
    return cfg.params["tile_rows"]


# (rows, block_rows): one sample in one tile; the engine's K lanes x 24
# rows (per-sample tiles, so per-lane partials regroup); and rows past
# one TPU tile, padded to whole tiles as ``ops`` does
RESID_CASES = {
    "one_tile": lambda: (SAMPLE_ROWS, SAMPLE_ROWS),
    "k_lanes": lambda: (LANES_K * SAMPLE_ROWS,
                        tuning.sample_tile_rows(SAMPLE_ROWS,
                                                _tpu_tile_rows())),
    "multi_tile_256": lambda: (2 * _tpu_tile_rows(), _tpu_tile_rows()),
}


@pytest.mark.parametrize("case", sorted(RESID_CASES))
def test_parareal_update_residual_compiles(one_chip, case):
    rows, br = RESID_CASES[case]()
    x = jax.ShapeDtypeStruct((rows, LANES), jnp.float32, sharding=one_chip)
    text = _compile_text(
        lambda y, c, p, o: parareal_update_residual_pallas(
            y, c, p, o, block_rows=br), x, x, x, x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows_of", [lambda: SAMPLE_ROWS,
                                     lambda: 2 * _tpu_tile_rows()],
                         ids=["one_tile", "multi_tile_256"])
def test_parareal_update_compiles(one_chip, rows_of):
    rows = rows_of()
    x = jax.ShapeDtypeStruct((rows, LANES), jnp.float32, sharding=one_chip)
    text = _compile_text(
        lambda y, c, p: parareal_update_pallas(
            y, c, p, block_rows=_tpu_tile_rows()), x, x, x)
    assert "tpu_custom_call" in text


def test_ddim_fused_compiles(one_chip):
    x = jax.ShapeDtypeStruct((LANES_K * SAMPLE_ROWS, LANES), jnp.float32,
                             sharding=one_chip)
    ab = jax.ShapeDtypeStruct((1, 2), jnp.float32, sharding=one_chip)
    text = _compile_text(
        lambda x, e, ab: ddim_fused_pallas(x, e, ab,
                                           block_rows=_tpu_tile_rows()),
        x, x, ab)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype, blocks",
                         [(jnp.float32, None), (jnp.bfloat16, None),
                          (jnp.bfloat16, 5)],
                         ids=["float32", "bfloat16", "bfloat16-vmap5"])
def test_flash_attention_fwd_compiles(one_chip, monkeypatch, dtype, blocks):
    # srds-dit-cifar attention: 4 slots x 12 heads, 64 patches, head_dim
    # 64, through the inference path the engine runs (the fine solves vmap
    # it over blocks), with the launch parameters the TPU tuning resolves
    s, d = 64, 64
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = tuning.get_tuner().resolve("flash", backend="tpu", dtype=dtype,
                                     shape=(s, s, d))
    assert cfg.params["block_b"] > 1
    tuner = tuning.KernelTuner(overrides={"flash": dict(cfg.params)})

    def attn(q, k, v):
        return ops.attention(q, k, v, causal=False, tuner=tuner,
                             plat="tpu", use_kernel=True)

    lead = (blocks, LANES_K) if blocks else (LANES_K,)
    q = jax.ShapeDtypeStruct((*lead, 12, s, d), dtype, sharding=one_chip)
    text = _compile_text(jax.vmap(attn) if blocks else attn, q, q, q)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    # the benchmark's flash roofline finds the kernel by this name; the
    # inference forward's one result is o (no logsumexp)
    assert re.match(r"\s*%\S*srds_flash_fwd\S* = [a-z0-9]+\[", calls[0])
