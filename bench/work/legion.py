"""Operations and bytes of the Legion executor's work, from shapes.

A tile call of the bitlinear kernel multiplies int8 activations
``[m, k]`` by ``bits``-bit packed weights ``[k, n]`` into int32 ``[m, n]``:
it needs ``2*m*k*n`` operations and reads ``m*k + k*n*bits/8`` bytes and
writes ``4*m*n``.  A Program's operations are those of its GEMM workloads
at the shapes they declare.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def tile_call(m: int, k: int, n: int, bits: int) -> Tuple[float, float]:
    """(operations, bytes) of one bitlinear tile call."""
    return 2.0 * m * k * n, float(m * k + k * n * bits / 8 + 4 * m * n)


def program_ops(workloads: Iterable) -> float:
    """Operations of every GEMM of a Program's stages (one layer each:
    the stages are lowered for the layers the spec holds)."""
    return float(sum(2 * w.m * w.k * w.n * w.count for w in workloads))
