"""BitNet b1.58 quantization (QAT) — the model side of the paper's workloads.

Training uses fake-quant with straight-through estimators (QAT, the BitNet
recipe [5]): ternary absmean weights + per-token absmax int8 activations.
Serving quantizes each projection once into a :class:`TernaryWeight` (int8
codes and one scale per stacked layer) that :func:`ternary_dot` consumes;
:class:`QuantizedTensor` holds the packed-ternary weights of the
``kernels/bitlinear`` Pallas kernel.

All functions are pure and differentiable where it matters.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.quant import packing

EPS = 1e-5


class QuantizedTensor(NamedTuple):
    """Real (serving-side) quantized weight: packed payload + scale."""

    packed: jnp.ndarray   # uint8 [..., K/ (8/bits)]
    scale: jnp.ndarray    # f32 scalar (absmean) — dequant = unpack * scale
    bits: int
    shape: tuple          # original unpacked shape

    def dequantize(self) -> jnp.ndarray:
        vals = packing.unpack(self.packed, self.bits).astype(jnp.float32)
        return (vals * self.scale).reshape(self.shape)


# --------------------------------------------------------------------------- #
# Weight quantization: absmean ternary (BitNet b1.58)
# --------------------------------------------------------------------------- #

def weight_scale(w: jnp.ndarray) -> jnp.ndarray:
    """gamma = mean(|W|) (per tensor)."""
    return jnp.mean(jnp.abs(w)) + EPS


def quantize_weight_ternary(w: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """W -> (Q in {-1,0,1} int8, gamma). dequant = Q * gamma."""
    gamma = weight_scale(w)
    q = jnp.clip(jnp.round(w / gamma), -1, 1).astype(jnp.int8)
    return q, gamma


def pack_weight_ternary(w: jnp.ndarray) -> QuantizedTensor:
    q, gamma = quantize_weight_ternary(w)
    return QuantizedTensor(
        packed=packing.pack_2bit(q.reshape(-1, q.shape[-1])),
        scale=gamma, bits=2, shape=w.shape,
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TernaryWeight:
    """Served BitLinear weight, quantized once from a latent weight.

    ``q``: int8 codes in {-1, 0, 1} with the latent shape.  ``gamma``:
    float32 absmean scales, one per slice of the leading ``gamma.ndim``
    axes of ``q`` (``[L]`` for a ``[L, ...]`` stacked leaf, a scalar once
    ``lax.scan`` has taken a layer).  Dequant = ``q * gamma``.
    """

    q: jnp.ndarray
    gamma: jnp.ndarray


@functools.partial(jax.jit, static_argnames=("stacked", "experts"))
def ternary_weight(w: jnp.ndarray, *, stacked: bool = True,
                   experts: bool = False) -> TernaryWeight:
    """:func:`quantize_weight_ternary` on each layer slice of ``w`` (the
    leading axis when ``stacked``, else the whole leaf) and, with
    ``experts``, on each expert of a layer (the next axis: every expert is
    a BitLinear of its own): the codes and gamma the fake-quant step
    computes from the same slice, gamma widened to float32 exactly."""
    quant = quantize_weight_ternary
    if experts:
        quant = functools.partial(jax.lax.map, quant)
    q, gamma = jax.lax.map(quant, w) if stacked else quant(w)
    return TernaryWeight(q, gamma.astype(jnp.float32))


def fake_quant_weight(w: jnp.ndarray) -> jnp.ndarray:
    """Ternary fake-quant with STE: forward = dequant(quant(w)), grad = 1."""
    q, gamma = quantize_weight_ternary(w)
    wq = q.astype(w.dtype) * gamma.astype(w.dtype)
    return w + jax.lax.stop_gradient(wq - w)


# --------------------------------------------------------------------------- #
# Activation quantization: per-token absmax int8 (BitNet)
# --------------------------------------------------------------------------- #

def act_scale(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    return jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0 + EPS


def quantize_act_int8(
    x: jnp.ndarray, axis: int = -1,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    s = act_scale(x, axis)
    q = jnp.clip(jnp.round(x / s), -128, 127).astype(jnp.int8)
    return q, s


def fake_quant_act(x: jnp.ndarray) -> jnp.ndarray:
    """Per-token int8 fake-quant with STE."""
    s = act_scale(x)
    xq = jnp.clip(jnp.round(x / s), -128, 127) * s
    return x + jax.lax.stop_gradient(xq.astype(x.dtype) - x)


# --------------------------------------------------------------------------- #
# BitLinear: y = act_fq(x) @ weight_fq(W)    (QAT path)
#            y = int8(x) @ codes * xs*gamma  (served TernaryWeight)
#            y = int8(x) @ unpack(W_packed) * scales (serving path)
# --------------------------------------------------------------------------- #

def bit_linear_train(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """QAT forward: fake-quantized activations x fake-quantized weights.

    ``x`` is assumed pre-normalized (BitLinear wraps RMSNorm before quant —
    done by the caller in ``models.layers``).
    """
    return fake_quant_act(x) @ fake_quant_weight(w)


def ternary_dot(
    x: jnp.ndarray, w: TernaryWeight, spec: str = "...k,kn->...n",
) -> jnp.ndarray:
    """BitLinear on a served weight: per-token absmax int8 activations times
    the ternary codes, contracted by the einsum ``spec`` and summed exactly
    in int32 (|sum| <= 128 * K < 2**31), scaled by ``xs * gamma`` in float32
    and cast to ``x.dtype``.  ``w.gamma`` must be a scalar (one layer's
    weight)."""
    xq, xs = quantize_act_int8(x)
    acc = jnp.einsum(spec, xq, w.q, preferred_element_type=jnp.int32)
    scale = xs.astype(jnp.float32) * w.gamma
    return (acc.astype(jnp.float32) * scale).astype(x.dtype)


def bit_linear_serve(
    x: jnp.ndarray, qw: QuantizedTensor, *, backend: str = "reference",
) -> jnp.ndarray:
    """Serving forward with real ternary weights.

    backend="reference": pure-jnp (dry-run / XLA path).
    backend="pallas":    kernels.bitlinear fused unpack+matmul (TPU path).
    """
    if backend == "pallas":
        from repro.kernels.bitlinear import ops as bl_ops
        # qw packs along N (what dequantize reads); the kernel streams
        # K-major packing, so the same values are repacked for it
        w = packing.unpack(qw.packed, qw.bits).reshape(qw.shape)
        pack_k = (packing.pack_2bit_kmajor if qw.bits == 2
                  else packing.pack_4bit_kmajor)
        xq, xs = quantize_act_int8(x)
        out = bl_ops.tile_gemm(xq, pack_k(w), bits=qw.bits, backend="pallas")
        return out.astype(x.dtype) * (xs * qw.scale).astype(x.dtype)
    w = qw.dequantize().astype(x.dtype)
    xq, xs = quantize_act_int8(x)
    return (xq.astype(x.dtype) * xs.astype(x.dtype)) @ w
