"""Plain NumPy reference of one attention block as the Legion Program runs
it, and the operands both sides use.

The block, in integers: ``qkv = x @ W`` per head (Q, K and V heads), int8
links between stages by symmetric per-tensor requantization
(``rint(a / max|a| * mag)``, ``mag`` 127), scores ``Q @ K^T`` with each
GQA group's K shared by its query heads, a row softmax of the scores scaled
by ``1 / (mag^2 * sqrt(head_dim))`` requantized to ``rint(p * mag)``, then
``P @ V`` and the output projection of the concatenated heads.

The streamed rows are made here from the seed.  The ternary weights are
drawn from the seed's NumPy generator in the order the lowering draws them
(Q, K, V head matrices, then the output projection), so both sides hold
the same operands without either taking the other's.
The control computes every int8 link at int4 (``mag`` 7, rows clipped to
[-8, 7]).
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from bench import traffic

STAGES = ("qkv_proj", "attn_score", "attn_output", "out_proj")


def weight_seed(seed: int) -> int:
    return seed & ((1 << 64) - 1)


def streamed_rows(block: dict, seed: int, count: int) -> List[np.ndarray]:
    """``count`` distinct int8 inputs [seq_len, hidden], one per Program."""
    return [traffic.rng(seed, 10, i).integers(
        -8, 9, size=(block["seq_len"], block["hidden"])).astype(np.int8)
        for i in range(count)]


def weights(block: dict, seed: int):
    lo, hi = {2: (-1, 2), 4: (-8, 8)}.get(block["weight_bits"], (-8, 9))
    h, g, hd, d = (block["heads"], block["kv_heads"], block["head_dim"],
                   block["hidden"])
    rng = np.random.default_rng(weight_seed(seed))
    wqkv = rng.integers(lo, hi, size=(h + 2 * g, d, hd)).astype(np.int8)
    wo = rng.integers(lo, hi, size=(1, h * hd, d)).astype(np.int8)
    return wqkv, wo


def _requant(a: np.ndarray, mag: int) -> np.ndarray:
    a = np.asarray(a, np.float64)
    peak = float(np.abs(a).max()) if a.size else 0.0
    if peak == 0.0:
        return np.zeros(a.shape, np.float64)
    return np.clip(np.rint(a / peak * mag), -mag, mag)


def _softmax(s: np.ndarray, scale: float, mag: int) -> np.ndarray:
    z = np.asarray(s, np.float64) * scale
    z = z - z.max(axis=-1, keepdims=True)
    p = np.exp(z)
    p = p / p.sum(axis=-1, keepdims=True)
    return np.rint(p * mag)


def outputs(block: dict, x: np.ndarray, wqkv: np.ndarray, wo: np.ndarray,
            mag: int = 127) -> Dict[str, np.ndarray]:
    """Every stage's ``[count, M, N]`` int32 outputs.  Products are exact in
    float64 at these sizes (every sum stays far below 2**53)."""
    h, g, hd = block["heads"], block["kv_heads"], block["head_dim"]
    gs = h // g
    xs = np.asarray(x, np.float64)
    if mag < 127:
        xs = np.clip(xs, -mag - 1, mag)
    qkv = np.einsum("sd,cde->cse", xs, wqkv.astype(np.float64))
    group = np.arange(h) // gs
    q = _requant(qkv[:h], mag)
    k = _requant(qkv[h:h + g], mag)[group]
    v = _requant(qkv[h + g:], mag)[group]
    score = np.einsum("hse,hte->hst", q, k)
    probs = _softmax(score, 1.0 / (127.0 * 127.0 * math.sqrt(hd)) *
                     (127.0 / mag) ** 2, mag)
    attn = np.einsum("hst,hte->hse", probs, v)
    cat = _requant(np.transpose(attn, (1, 0, 2)).reshape(x.shape[0], h * hd),
                   mag)
    out = (cat @ wo[0].astype(np.float64))[None]
    return {name: arr.astype(np.int32) for name, arr in
            zip(STAGES, (qkv, score, attn, out))}


def mismatches(got: Dict[str, np.ndarray],
               want: Dict[str, np.ndarray]) -> int:
    """Elements of every stage that differ (a missing or misshapen stage
    counts whole)."""
    bad = 0
    for name, w in want.items():
        g = got.get(name)
        if g is None or np.shape(g) != w.shape:
            bad += w.size
        else:
            bad += int(np.count_nonzero(np.asarray(g) != w))
    return bad
