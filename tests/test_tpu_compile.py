"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Every other kernel test runs in interpret mode, which accepts what Mosaic
refuses (unaligned blocks, 8-bit shifts, unsupported reshapes).  Here each
kernel is compiled for one chip of a described ``v5e:2x2`` topology, which
needs no chip: only the TPU compiler.  A compiled kernel shows up in the
executable as a ``tpu_custom_call``; interpret mode would lower to plain HLO.

The topology is described inside a fixture (never at import), so every test
worker collects the same tests and only the worker given this file loads
the TPU library.  The persistent compilation cache is off here: entries
compiled for a described chip cannot be read back without one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitlinear.kernel import bitlinear_matmul
from repro.kernels.block_sparse.kernel import block_sparse_matmul
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.ssd.kernel import ssd_scan


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; returns the HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,k,n,bits,blocks", [
    # a BitNet-1.58B projection at the default MXU blocks, 2- and 4-bit
    (128, 2560, 2048, 2, {}),
    (128, 2560, 2048, 4, {}),
    # the Legion executor's window tile under dlegion(): K = C*D = 128,
    # N = R*D = 64, one grid cell, at decode (M=1) and prefill (M=128) rows
    (1, 128, 64, 2, dict(bm=1, bk=128, bn=64)),
    (128, 128, 64, 2, dict(bm=128, bk=128, bn=64)),
])
def test_bitlinear_compiles(one_chip, m, k, n, bits, blocks):
    fn = functools.partial(bitlinear_matmul, bits=bits, **blocks)
    hlo = _compile(fn, one_chip, ((m, k), jnp.int8),
                   ((k * bits // 8, n), jnp.uint8))
    assert "tpu_custom_call" in hlo


def test_block_sparse_compiles(one_chip):
    m, k, n, blk = 256, 512, 256, 128
    fn = functools.partial(block_sparse_matmul, bm=blk, bn=blk, bk=blk)
    hlo = _compile(fn, one_chip, ((m, k), jnp.float32),
                   ((k, n), jnp.float32),
                   ((n // blk, k // blk), jnp.int32), ((n // blk,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles(one_chip):
    bh, s, d = 16, 2048, 128
    fn = functools.partial(flash_attention, q_heads=16, kv_heads=16)
    hlo = _compile(fn, one_chip, *[((bh, s, d), jnp.bfloat16)] * 3)
    assert "tpu_custom_call" in hlo


def test_ssd_compiles_at_mamba2_130m_widths(one_chip):
    # mamba2-130m: d_inner 1536 / head_dim 64 = 24 heads, state 128,
    # chunk 128; one sequence of 2048 tokens
    bh, s, p, n = 24, 2048, 64, 128
    fn = functools.partial(ssd_scan, chunk=128)
    hlo = _compile(fn, one_chip, ((bh, s), jnp.float32),
                   ((bh, s, p), jnp.float32), ((bh, s, n), jnp.float32),
                   ((bh, s, n), jnp.float32))
    assert "tpu_custom_call" in hlo
