"""Pallas TPU kernel: fused unpack + matmul for packed-ternary weights.

This is the TPU-native re-materialization of D-Legion's projection mode
(8b x 2b, R = 4): weights stream from HBM packed 4-per-byte, are unpacked
**in VMEM**, and partial sums accumulate across the K grid dimension in a
float32/int32 VMEM scratch — written back to HBM exactly once.

Mapping of paper concepts:

    ADiP core (D x D)            -> one (bm x bn) MXU-aligned output block
    C cores K-split per Legion   -> the K grid dimension
    Legion parallel accumulators -> the VMEM ``acc_ref`` scratch (psums are
                                    spatially reduced before ever touching
                                    HBM — zero psum RMW traffic)
    2-bit weight packing (R=4)   -> 4x fewer weight bytes over the HBM->VMEM
                                    edge (the bandwidth-bound axis on TPU)

Block shapes default to MXU-aligned (128, 128) tiles with bk=256 packed
K rows (64 bytes of packed payload per lane).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _unpack_kmajor_inkernel(packed: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Kernel-safe K-major unpack (scalar shift constants only — Pallas
    kernels may not capture array constants).

    The bit work runs on int32: Mosaic has no 8-bit vector shifts, and the
    sublane interleave below only lowers for 32-bit values.  Field ``i`` is
    shifted to the top of the word and arithmetic-shifted back down, which
    sign-extends it; the values are narrowed to int8 for the MXU last.
    """
    per_byte = 8 // bits
    word = packed.astype(jnp.int32)
    parts = [
        jnp.right_shift(jnp.left_shift(word, 32 - bits * (i + 1)), 32 - bits)
        for i in range(per_byte)
    ]
    stacked = jnp.stack(parts, axis=1)  # [bk/pb, pb, bn] — sublane expand
    return stacked.reshape(
        packed.shape[0] * per_byte, packed.shape[1]).astype(jnp.int8)


def _bitlinear_kernel(x_ref, wp_ref, out_ref, acc_ref, *, n_k_tiles: int,
                      bits: int, out_dtype):
    """grid = (M/bm, N/bn, K/bk); K innermost accumulates into acc_ref."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                      # [bm, bk] int8
    wp = wp_ref[...]                    # [bk // (8/bits), bn] uint8
    w = _unpack_kmajor_inkernel(wp, bits)   # [bk, bn] int8
    acc_ref[...] += jax.lax.dot_general(
        x, w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k_step == n_k_tiles - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "bm", "bn", "bk", "interpret", "out_dtype"),
)
def bitlinear_matmul(
    x: jnp.ndarray,
    w_packed: jnp.ndarray,
    *,
    bits: int = 2,
    bm: int = 128,
    bn: int = 128,
    bk: int = 256,
    interpret: bool = False,
    out_dtype=jnp.int32,
) -> jnp.ndarray:
    """``out[M, N] = x[M, K] @ unpack(w_packed)[K, N]`` (int32 accumulate).

    Args:
      x: int8 [M, K].
      w_packed: uint8 [K // (8/bits), N] — K-major packed (see quant.packing).
      bits: weight precision (2 = ternary projection mode, 4 = 8bx4b mode).
      bm/bn/bk: VMEM block shape (MXU-aligned multiples of 128 on TPU).
      interpret: run the kernel body in Python (CPU validation).
    """
    m, k = x.shape
    factor = 8 // bits
    kq, n = w_packed.shape
    if kq * factor != k:
        raise ValueError(f"packed K {kq}*{factor} != {k}")
    if m % bm or n % bn or k % bk:
        raise ValueError(f"({m},{k},{n}) not divisible by ({bm},{bk},{bn})")
    n_k_tiles = k // bk
    grid = (m // bm, n // bn, n_k_tiles)

    kernel = functools.partial(
        _bitlinear_kernel, n_k_tiles=n_k_tiles, bits=bits, out_dtype=out_dtype,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk // factor, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x, w_packed)
