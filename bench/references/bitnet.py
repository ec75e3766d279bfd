"""Plain reference of a BitNet b1.58 decoder, and the weights both sides use.

The weights are made here from the seed, never by the program: a served run
gets them as one pytree from :func:`served_weights`, and the reference makes
each layer again from the same seed with :func:`layer_weights`, one layer at
a time, after the program's state is freed.

The block is the pre-norm LLaMA-style BitNet b1.58 decoder that the
configuration states: RMSNorm (eps ``rms_eps``) before attention and before
the MLP, rotary positions (rotate-half, base ``rope_theta``), grouped-query
attention with softmax over ``1/sqrt(head_dim)`` scores, a SwiGLU MLP, a
final RMSNorm and a head tied to the token embedding.  Every projection is a
BitLinear: per-token absmax int8 activations (scale ``max|x|/127 + eps``)
times absmean ternary weights (``gamma = mean|W| + eps``, values
``clip(round(W/gamma), -1, 1) * gamma``).  Departure from BitNet (2023): no
extra norm inside BitLinear before the output and down projections.

Everything is float32 with matmuls at ``highest`` precision.  ``rnd`` is the
rounding applied wherever a served model stores a value in its own dtype:
the identity for the reference, a cast through float8 e4m3 for the control.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PROJ = ("wq", "wk", "wv", "wo", "w1", "w2", "w3")
NORMS = ("ln1", "ln2")
_EMBED_TAG, _LN_F_TAG, _LAYER_TAG0 = 0, 1, 16
FP8_MAX = 448.0


def identity(x):
    return x


def fp8(x):
    """Round to float8 e4m3 (saturating) and widen back: the control."""
    x = jnp.clip(x, -FP8_MAX, FP8_MAX)
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def base_key(seed: int) -> jnp.ndarray:
    """A raw threefry key from any whole-number seed (63-bit mixing)."""
    state = np.random.SeedSequence(seed & ((1 << 64) - 1)).generate_state(2)
    return jnp.asarray(state, jnp.uint32)


def _shapes(m: dict) -> Dict[str, tuple]:
    d, hd, ff = m["hidden_size"], m["head_dim"], m["intermediate_size"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    return {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
            "wo": (h * hd, d), "w1": (d, ff), "w2": (ff, d), "w3": (d, ff),
            "ln1": (d,), "ln2": (d,)}


def _layer_f32(m: dict, key, layer) -> Dict[str, jnp.ndarray]:
    """One layer's weights in float32, before rounding to the served dtype.
    Projections ~ N(0, 1/fan_in); norm gains ~ 1 + N(0, 0.1^2), so a
    program that skips a gain is seen."""
    k = jax.random.fold_in(key, _LAYER_TAG0 + layer)
    out = {}
    for i, (name, shape) in enumerate(_shapes(m).items()):
        z = jax.random.normal(jax.random.fold_in(k, i), shape, jnp.float32)
        out[name] = (1.0 + 0.1 * z if name in NORMS
                     else z / math.sqrt(shape[0]))
    return out


def _embed_f32(m: dict, key):
    shape = (m["vocab_size"], m["hidden_size"])
    return 0.02 * jax.random.normal(jax.random.fold_in(key, _EMBED_TAG),
                                    shape, jnp.float32)


def _ln_f_f32(m: dict, key):
    z = jax.random.normal(jax.random.fold_in(key, _LN_F_TAG),
                          (m["hidden_size"],), jnp.float32)
    return 1.0 + 0.1 * z


def served_weights(m: dict, seed: int, dtype) -> Dict[str, object]:
    """Every weight in ``dtype`` from one jitted call on the device:
    ``{"embed", "ln_f", "layers": {leaf: [L, ...]}}``."""
    layers = m["num_hidden_layers"]

    @jax.jit
    def make(key):
        stacked = jax.lax.map(
            lambda l: {k: v.astype(dtype)
                       for k, v in _layer_f32(m, key, l).items()},
            jnp.arange(layers, dtype=jnp.int32))
        return {"embed": _embed_f32(m, key).astype(dtype),
                "ln_f": _ln_f_f32(m, key).astype(dtype),
                "layers": stacked}

    return make(base_key(seed))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_weights(mt, key, layer, dtype):
    m = dict(mt)
    return {k: v.astype(dtype).astype(jnp.float32)
            for k, v in _layer_f32(m, key, layer).items()}


def layer_weights(m: dict, seed: int, layer: int, dtype):
    """Layer ``layer`` as served (rounded to ``dtype``), widened to f32."""
    return _layer_weights(_frozen(m), base_key(seed),
                          jnp.int32(layer), jnp.dtype(dtype))


def _frozen(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


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
    return rnd(jnp.matmul(rnd(xq), rnd(wq),
                          precision=jax.lax.Precision.HIGHEST))


def _rope(x, theta):
    """x [n, T, H, hd]; rotate-half rotary positions 0..T-1."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(mt, rnd, w, x):
    m = dict(mt)
    n, t, _ = x.shape
    h, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    eps, qeps = m["rms_norm_eps"], m["quant_eps"]
    w = {k: rnd(v) for k, v in w.items()}
    a = rnd(_rms_norm(x, w["ln1"], eps))
    q = _bitlinear(a, w["wq"], qeps, rnd).reshape(n, t, kv, h // kv, hd)
    k = _bitlinear(a, w["wk"], qeps, rnd).reshape(n, t, kv, hd)
    v = _bitlinear(a, w["wv"], qeps, rnd).reshape(n, t, kv, hd)
    q = rnd(_rope(q.reshape(n, t, h, hd), m["rope_theta"])) \
        .reshape(n, t, kv, h // kv, hd)
    k = rnd(_rope(k, m["rope_theta"]))
    hi = jax.lax.Precision.HIGHEST
    sc = jnp.einsum("nqkgd,ntkd->nkgqt", q, k, precision=hi) / math.sqrt(hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
    o = rnd(jnp.einsum("nkgqt,ntkd->nqkgd", p, v, precision=hi)
            ).reshape(n, t, h * hd)
    x = rnd(x + _bitlinear(o, w["wo"], qeps, rnd))
    b = rnd(_rms_norm(x, w["ln2"], eps))
    g = rnd(jax.nn.silu(_bitlinear(b, w["w1"], qeps, rnd))
            * _bitlinear(b, w["w3"], qeps, rnd))
    return rnd(x + _bitlinear(g, w["w2"], qeps, rnd))


_layer_jit = jax.jit(_layer, static_argnums=(0, 1))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(mt, rnd, embed, ln_f, x):
    m = dict(mt)
    y = rnd(_rms_norm(x, rnd(ln_f), m["rms_norm_eps"]))
    return rnd(jnp.matmul(y, rnd(embed).T,
                          precision=jax.lax.Precision.HIGHEST))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _embed_rows(mt, rnd, embed, tokens):
    return rnd(rnd(embed)[tokens])


@functools.partial(jax.jit, static_argnums=(0,))
def _embed_all(mt, key):
    return _embed_f32(dict(mt), key)


@functools.partial(jax.jit, static_argnums=(0,))
def _ln_f_all(mt, key):
    return _ln_f_f32(dict(mt), key)


def forward_logits(m: dict, seed: int, tokens: np.ndarray, dtype,
                   rnd: Callable = identity) -> jnp.ndarray:
    """Logits [n, T, V] of the whole model over ``tokens`` [n, T], causal,
    one layer's weights on the device at a time."""
    mt = _frozen(m)
    key = base_key(seed)
    dt = jnp.dtype(dtype)
    embed = _embed_all(mt, key).astype(dt).astype(jnp.float32)
    x = _embed_rows(mt, rnd, embed, jnp.asarray(tokens, jnp.int32))
    for layer in range(m["num_hidden_layers"]):
        w = _layer_weights(mt, key, jnp.int32(layer), dt)
        x = _layer_jit(mt, rnd, w, x)
    ln_f = _ln_f_all(mt, key).astype(dt).astype(jnp.float32)
    return _head(mt, rnd, embed, ln_f, x)


@jax.jit
def _gaps(logits, targets):
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return best - got


def token_gaps(m: dict, seed: int, tokens: np.ndarray,
               targets: Sequence[np.ndarray], dtype) -> list:
    """For each target array [n, T]: how far the reference's logit of the
    target token lies below its best logit, at every position."""
    logits = forward_logits(m, seed, tokens, dtype)
    return [np.asarray(_gaps(logits, jnp.asarray(t, jnp.int32)))
            for t in targets]


def control_tokens(m: dict, seed: int, tokens: np.ndarray,
                   dtype) -> np.ndarray:
    """The token the reference computed in float8 puts first, per position."""
    logits = forward_logits(m, seed, tokens, dtype, rnd=fp8)
    return np.asarray(jnp.argmax(logits, -1))
