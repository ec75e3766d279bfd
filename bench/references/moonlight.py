"""Plain reference of Moonlight-16B-A3B (the DeepSeek-V3 block) on one
chip's share of its experts, and the weights both sides use.

The weights are made here from the seed, never by the program: a served run
gets them as one pytree from :func:`served_weights`, and the reference makes
each layer again from the same seed, one layer at a time, after the
program's state is freed.  A routed expert's weights are drawn by its
global id, so every share of a layer holds the same experts the uncut
layer holds.

The block follows ``transformers``' ``modeling_deepseek_v3.py`` (4.57):
pre-norm RMSNorm (eps ``rms_norm_eps``) before attention and before the
FFN; multi-head latent attention with a plain ``q_proj`` (``q_lora_rank``
null): ``[c, k_pe] = kv_a_proj_with_mqa(x)``, ``c`` normalised by
``kv_a_layernorm``, ``kv_b_proj(c)`` split into each head's K_nope and V,
the rotary key shared by the heads, scores over ``1/sqrt(qk_nope +
qk_rope)``, causal softmax; then ``o_proj``.  Layers below
``first_k_dense_replace`` have a SwiGLU MLP of ``intermediate_size``; the
rest are expert layers: sigmoid scores of a float32 router over all
``n_routed_experts``, the top ``num_experts_per_tok`` chosen by score plus
``e_score_correction_bias`` (one group), gates the unbiased scores,
renormalised (``norm_topk_prob``) and times ``routed_scaling_factor``;
the chosen experts' SwiGLUs of ``moe_intermediate_size`` weighted by their
gates, plus a shared SwiGLU of ``n_shared_experts * moe_intermediate_size``.
A final RMSNorm and an untied head.

Every projection is a BitLinear (the repo's W1.58A8): per-token absmax
int8 activations (scale ``max|x|/127 + eps``) times absmean ternary
weights (``gamma = mean|W| + eps`` per projection, and per expert).  The
router, the norms, the embedding and the head are not quantized.

Departures from ``modeling_deepseek_v3.py``, each also the program's:

* BitLinear projections, as above (the checkpoint is bf16);
* rotate-half rotary positions on the 64 rope dims, where the checkpoint's
  ``rope_interleave`` rotates interleaved pairs: the same up to a fixed
  permutation of the rope weights, which are random here;
* the expert share: of each expert layer only the experts
  ``[expert_offset, expert_offset + experts_held)`` add their part, as on
  one chip of an expert-parallel deployment; the router still spans every
  expert;
* no ``+ 1e-20`` in the gates' renormalisation (the sum of six sigmoid
  scores is far above it).

Everything is float32 with matmuls at ``highest`` precision, in the
expanded form of latent attention.  ``rnd`` is the rounding applied
wherever a served model stores a value in its own dtype: the identity for
the reference, a cast through float8 e4m3 for the control.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.references.bitnet import base_key, fp8, identity

_EMBED_TAG, _LN_F_TAG, _HEAD_TAG, _LAYER_TAG0 = 0, 1, 2, 16
_EXPERT_TAG0 = 1 << 12
NORMS = ("ln1", "ln2", "kv_norm")
FLOAT32 = ("router", "router_bias")     # served in float32 as they are
HI = jax.lax.Precision.HIGHEST


def _attn_shapes(m: dict) -> Dict[str, tuple]:
    d, h, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    return {"ln1": (d,), "ln2": (d,), "wq": (d, h * (nope + rope)),
            "wkv_a": (d, r + rope), "kv_norm": (r,),
            "wkv_b": (r, h * (nope + v)), "wo": (h * v, d)}


def _mlp_shapes(d: int, ff: int, prefix: str = "") -> Dict[str, tuple]:
    return {prefix + "w1": (d, ff), prefix + "w2": (ff, d),
            prefix + "w3": (d, ff)}


def is_dense(m: dict, layer: int) -> bool:
    return layer < m["first_k_dense_replace"]


def _leaf(key, name, shape):
    z = jax.random.normal(key, shape, jnp.float32)
    if name in NORMS:
        return 1.0 + 0.1 * z                  # gains ~ 1 + N(0, 0.1^2)
    if name == "router_bias":
        return 0.1 * z                        # assumed: see the config
    return z / math.sqrt(shape[-2])           # N(0, 1/fan_in)


def _layer_f32(m: dict, key, layer, dense: bool) -> Dict[str, jnp.ndarray]:
    """One layer's weights in float32, before rounding to the served
    dtype; a held expert ``j`` is the layer's expert ``offset + j``."""
    d = m["hidden_size"]
    k = jax.random.fold_in(key, _LAYER_TAG0 + layer)
    shapes = dict(_attn_shapes(m))
    if dense:
        shapes.update(_mlp_shapes(d, m["intermediate_size"]))
    else:
        e, f = m["n_routed_experts"], m["moe_intermediate_size"]
        shapes.update({"router": (d, e), "router_bias": (e,)})
        shapes.update(_mlp_shapes(d, f * m["n_shared_experts"], "shared_"))
    out = {name: _leaf(jax.random.fold_in(k, i), name, shape)
           for i, (name, shape) in enumerate(shapes.items())}
    if not dense:
        f = m["moe_intermediate_size"]
        ids = m["expert_offset"] + jnp.arange(m["experts_held"])

        def expert(g):
            ke = jax.random.fold_in(k, _EXPERT_TAG0 + g)
            return {"expert_" + n: _leaf(jax.random.fold_in(ke, i), n, s)
                    for i, (n, s) in enumerate(_mlp_shapes(d, f).items())}

        out.update(jax.vmap(expert)(ids))
    return out


def _embed_f32(m: dict, key):
    shape = (m["vocab_size"], m["hidden_size"])
    return 0.02 * jax.random.normal(jax.random.fold_in(key, _EMBED_TAG),
                                    shape, jnp.float32)


def _head_f32(m: dict, key):
    shape = (m["hidden_size"], m["vocab_size"])
    z = jax.random.normal(jax.random.fold_in(key, _HEAD_TAG), shape,
                          jnp.float32)
    return z / math.sqrt(shape[0])


def _ln_f_f32(m: dict, key):
    z = jax.random.normal(jax.random.fold_in(key, _LN_F_TAG),
                          (m["hidden_size"],), jnp.float32)
    return 1.0 + 0.1 * z


def _cast(w: dict, dtype) -> dict:
    return {k: v if k in FLOAT32 else v.astype(dtype) for k, v in w.items()}


def served_weights(m: dict, seed: int, dtype) -> Dict[str, object]:
    """Every weight in ``dtype`` (router and bias in float32) from one
    jitted call on the device: ``{"embed", "lm_head", "ln_f",
    "dense_layers": {leaf: [k, ...]}, "layers": {leaf: [L - k, ...]}}``."""
    lead, layers = m["first_k_dense_replace"], m["num_hidden_layers"]

    @jax.jit
    def make(key):
        def stack(lo, hi, dense):
            return jax.lax.map(
                lambda l: _cast(_layer_f32(m, key, l, dense), dtype),
                jnp.arange(lo, hi, dtype=jnp.int32))

        return {"embed": _embed_f32(m, key).astype(dtype),
                "lm_head": _head_f32(m, key).astype(dtype),
                "ln_f": _ln_f_f32(m, key).astype(dtype),
                "dense_layers": stack(0, lead, True),
                "layers": stack(lead, layers, False)}

    return make(base_key(seed))


def _frozen(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _layer_weights(mt, key, layer, dense, dtype):
    w = _cast(_layer_f32(dict(mt), key, layer, dense), dtype)
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def layer_weights(m: dict, seed: int, layer: int, dtype):
    """Layer ``layer`` as served (rounded to ``dtype``), widened to f32."""
    return _layer_weights(_frozen(m), base_key(seed), jnp.int32(layer),
                          is_dense(m, layer), jnp.dtype(dtype))


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #

def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _bitlinear(x, w, eps, rnd):
    s = jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0 + eps
    xq = jnp.clip(jnp.round(x / s), -128, 127) * s
    gamma = jnp.mean(jnp.abs(w)) + eps
    wq = jnp.clip(jnp.round(w / gamma), -1, 1) * gamma
    return rnd(jnp.matmul(rnd(xq), rnd(wq), precision=HI))


def _swiglu(x, w1, w2, w3, eps, rnd):
    h = rnd(jax.nn.silu(_bitlinear(x, w1, eps, rnd))
            * _bitlinear(x, w3, eps, rnd))
    return _bitlinear(h, w2, eps, rnd)


def _rope(x, theta):
    """x [n, T, H, e]; rotate-half rotary positions 0..T-1."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(m, rnd, w, a):
    n, t, _ = a.shape
    h, r = m["num_attention_heads"], m["kv_lora_rank"]
    nope, v = m["qk_nope_head_dim"], m["v_head_dim"]
    qeps = m["quant_eps"]
    q = _bitlinear(a, w["wq"], qeps, rnd).reshape(n, t, h, -1)
    kv = _bitlinear(a, w["wkv_a"], qeps, rnd)
    c = rnd(_rms_norm(kv[..., :r], w["kv_norm"], m["rms_norm_eps"]))
    k_pe = rnd(_rope(kv[..., None, r:], m["rope_theta"]))    # [n, T, 1, e]
    q_pe = rnd(_rope(q[..., nope:], m["rope_theta"]))
    kvb = _bitlinear(c, w["wkv_b"], qeps, rnd).reshape(n, t, h, nope + v)
    qf = jnp.concatenate([q[..., :nope], q_pe], -1)
    kf = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_pe, (n, t, h, k_pe.shape[-1]))],
        -1)
    sc = jnp.einsum("nqhd,nthd->nhqt", qf, kf, precision=HI) \
        / math.sqrt(qf.shape[-1])
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
    o = rnd(jnp.einsum("nhqt,nthd->nqhd", p, kvb[..., nope:], precision=HI))
    return _bitlinear(o.reshape(n, t, h * v), w["wo"], qeps, rnd)


def route(m: dict, b, router, bias):
    """(gates [..., held], chose [..., held]): each token's gate on each
    held expert, zero where the token did not choose it."""
    scores = jax.nn.sigmoid(jnp.matmul(b, router, precision=HI))
    _, idx = jax.lax.top_k(scores + bias, m["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, idx, -1)
    if m["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * m["routed_scaling_factor"]
    held = m["expert_offset"] + jnp.arange(m["experts_held"])
    chose = idx[..., None] == held                         # [..., k, held]
    return jnp.sum(jnp.where(chose, gates[..., None], 0.0), -2), \
        jnp.any(chose, -2)


def _experts(m, rnd, w, b):
    """The held experts' part of the layer for every token: each token's
    chosen held experts, weighted by their gates."""
    qeps = m["quant_eps"]
    g, _ = route(m, b, w["router"], w["router_bias"])
    out = jax.vmap(lambda w1, w2, w3: _swiglu(b, w1, w2, w3, qeps, rnd))(
        w["expert_w1"], w["expert_w2"], w["expert_w3"])     # [held, n, T, d]
    return jnp.einsum("ntj,jntd->ntd", g, out, precision=HI)


def _layer(mt, rnd, dense, w, x):
    m = dict(mt)
    eps, qeps = m["rms_norm_eps"], m["quant_eps"]
    w = {k: rnd(v) for k, v in w.items()}
    x = rnd(x + _attention(m, rnd, w, rnd(_rms_norm(x, w["ln1"], eps))))
    b = rnd(_rms_norm(x, w["ln2"], eps))
    if dense:
        y = _swiglu(b, w["w1"], w["w2"], w["w3"], qeps, rnd)
    else:
        y = rnd(_experts(m, rnd, w, b)) + _swiglu(
            b, w["shared_w1"], w["shared_w2"], w["shared_w3"], qeps, rnd)
    return rnd(x + y)


_layer_jit = jax.jit(_layer, static_argnums=(0, 1, 2))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _embed_rows(mt, rnd, dtype, key, tokens):
    embed = _embed_f32(dict(mt), key).astype(dtype).astype(jnp.float32)
    return rnd(rnd(embed)[tokens])


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _head_tables(mt, rnd, dtype, key):
    m = dict(mt)
    head = _head_f32(m, key).astype(dtype).astype(jnp.float32)
    ln_f = _ln_f_f32(m, key).astype(dtype).astype(jnp.float32)
    return rnd(head), rnd(ln_f)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _row_logits(mt, rnd, head, ln_f, x):
    y = rnd(_rms_norm(x, ln_f, dict(mt)["rms_norm_eps"]))
    return rnd(jnp.matmul(y, head, precision=HI))             # [T, V]


@jax.jit
def _gaps(logits, targets):
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return best - got


def _hidden(m: dict, seed: int, tokens: np.ndarray, dtype, rnd):
    """The last layer's output [n, T, d], one layer's weights on the
    device at a time."""
    mt = _frozen(m)
    key = base_key(seed)
    dt = jnp.dtype(dtype)
    x = _embed_rows(mt, rnd, dt, key, jnp.asarray(tokens, jnp.int32))
    for layer in range(m["num_hidden_layers"]):
        dense = is_dense(m, layer)
        w = _layer_weights(mt, key, jnp.int32(layer), dense, dt)
        x = _layer_jit(mt, rnd, dense, w, x)
    return x


def _per_row(m, seed, tokens, dtype, rnd, fn):
    """``fn(logits [T, V], row)`` for each sequence: the head runs one
    sequence at a time, so that ``n * T * V`` logits are never held."""
    x = _hidden(m, seed, tokens, dtype, rnd)
    head, ln_f = _head_tables(_frozen(m), rnd, jnp.dtype(dtype),
                              base_key(seed))
    return [fn(_row_logits(_frozen(m), rnd, head, ln_f, x[i]), i)
            for i in range(x.shape[0])]


def token_gaps(m: dict, seed: int, tokens: np.ndarray,
               targets: Sequence[np.ndarray], dtype) -> list:
    """For each target array [n, T]: how far the reference's logit of the
    target token lies below its best logit, at every position."""
    rows = _per_row(m, seed, tokens, dtype, identity, lambda lg, i: [
        np.asarray(_gaps(lg, jnp.asarray(t[i], jnp.int32))) for t in targets])
    return [np.stack([r[j] for r in rows]) for j in range(len(targets))]


def control_tokens(m: dict, seed: int, tokens: np.ndarray,
                   dtype) -> np.ndarray:
    """The token the reference computed in float8 puts first, per position."""
    return np.stack(_per_row(m, seed, tokens, dtype, fp8,
                             lambda lg, i: np.asarray(jnp.argmax(lg, -1))))


def readings(m: dict, seed: int, tokens: np.ndarray, targets: np.ndarray,
             mask: np.ndarray, dtype) -> dict:
    """The number compared, for the program (its served tokens
    ``targets``) and for its control (the float8 reference in the
    program's place): the widest gap of the token each puts first."""
    ctrl = control_tokens(m, seed, tokens, dtype)
    prog, low = token_gaps(m, seed, tokens, [targets, ctrl], dtype)
    return {"program": float(prog[mask].max()),
            "control": float(low[mask].max()), "tokens": int(mask.sum())}


def sizes(config: dict) -> dict:
    """The numbers the reference and the work counts read, from a
    configuration file."""
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
            "first_k_dense_replace", "routed_scaling_factor",
            "norm_topk_prob", "vocab_size", "rms_norm_eps", "rope_theta",
            "quant_eps", "experts_held", "expert_offset")
    return {k: config[k] for k in keys}

