"""Pallas TPU kernel: Mamba-2 SSD (state-space duality) chunked scan.

Needed for the assigned SSM/hybrid architectures (mamba2-130m, zamba2-7b).
The SSD trick is itself a D-Legion-friendly decomposition: each chunk's
quadratic intra-chunk block is a dense GEMM (MXU work), and the inter-chunk
state carry is a small [N, P] tensor that lives in VMEM scratch across grid
steps — on-chip state carry, the same "psums never round-trip HBM" principle
as the Legion accumulators.

Inputs are pre-scaled outside the kernel (dta = dt * A  [negative],
dtx = dt * x), so the kernel is free of per-head scalars:

    h_c      = exp(sum(dta_c)) * h_{c-1} + (B_c * decay_out)^T @ dtx_c
    y_c[i]   = ((C_c B_c^T) o L)_i @ dtx_c  +  (C_c[i] * exp(la_i)) @ h_{c-1}
    L_ij     = exp(la_i - la_j) for i >= j else 0,   la = cumsum(dta_c)

Grid: (batch*heads, n_chunks) — chunks innermost, state carried in scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(
    la_ref,     # (1, 1, q)  f32 — la = within-chunk cumsum(dt * A), a row
    dtx_ref,    # (1, q, p)  f32 — dt * x
    b_ref,      # (1, q, n)  f32
    c_ref,      # (1, q, n)  f32
    out_ref,    # (1, q, p)
    h_ref,      # VMEM scratch (n, p) f32 — inter-chunk state
    *, q: int,
):
    chunk = pl.program_id(1)

    @pl.when(chunk == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    la_row = la_ref[0]                          # [1, q] log-decay from start
    dtx = dtx_ref[0].astype(jnp.float32)        # [q, p]
    b = b_ref[0].astype(jnp.float32)            # [q, n]
    c = c_ref[0].astype(jnp.float32)            # [q, n]

    # la arrives as a lane row; the column form comes from a 2-D transpose
    la_j = jnp.broadcast_to(la_row, (q, q))     # [i, j] -> la_j
    la_i = la_j.T                               # [i, j] -> la_i
    la_col = la_i[:, 0:1]                       # [q, 1]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    decay = jnp.exp(jnp.where(ii >= jj, la_i - la_j, NEG_INF))  # causal L

    scores = jax.lax.dot_general(               # (C B^T) o L
        c, b, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * decay
    y = jax.lax.dot_general(                    # intra-chunk
        scores, dtx, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    h_prev = h_ref[...]                         # [n, p]
    y += jax.lax.dot_general(                   # inter-chunk (state readout)
        c * jnp.exp(la_col), h_prev,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    la_total = la_row[:, q - 1:q]               # [1, 1]
    decay_out = jnp.exp(la_total - la_col)      # [q, 1]
    h_ref[...] = jnp.exp(la_total) * h_prev + jax.lax.dot_general(
        b * decay_out, dtx,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    out_ref[0, ...] = y.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    dta: jnp.ndarray,     # [BH, S]      f32 (dt * A, negative)
    dtx: jnp.ndarray,     # [BH, S, P]   (dt * x)
    b: jnp.ndarray,       # [BH, S, N]
    c: jnp.ndarray,       # [BH, S, N]
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    bh, s = dta.shape
    _, _, p = dtx.shape
    n = b.shape[-1]
    if s % chunk:
        raise ValueError(f"S={s} not divisible by chunk={chunk}")
    # The within-chunk cumsum runs in XLA; the kernel takes it as [BH, 1, S]
    # so each (1, chunk) block spans the whole sublane dim (TPU tiling rule).
    la = jnp.cumsum(
        dta.astype(jnp.float32).reshape(bh, s // chunk, chunk), axis=-1,
    ).reshape(bh, 1, s)
    kernel = functools.partial(_ssd_kernel, q=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, s // chunk),
        in_specs=[
            pl.BlockSpec((1, 1, chunk), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, p), dtx.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(la, dtx, b, c)
