"""Token-choice top-k MoE: capacity-based dispatch (expert parallelism
over a mesh), or one chip's held share of the experts, dropless.

Experts shard over the "model" mesh axis (EP) — each expert is a Legion-like
independent worker; tokens route via scatter/gather, which XLA SPMD turns
into the expected all-to-all pattern.  The ZTB analogy: an expert with no
routed tokens is a fully-sparse window — XLA still executes the (empty)
GEMM, but the simulator and sparse serving path skip it.

A config with ``experts_held`` is one chip's share of an expert-parallel
deployment (:func:`held_experts`): it routes over every expert and computes
only its own experts' part of the result, for every token routed to them.
A shared expert (``n_shared_experts``) is added for every token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models.common import dense_init, swiglu
from repro.quant.bitnet import (
    TernaryWeight,
    fake_quant_act,
    fake_quant_weight,
    quantize_act_int8,
    ternary_dot,
)


def init_moe_params(key, cfg, dtype) -> dict:
    if cfg.experts_held:
        return _init_held(key, cfg, dtype)
    ks = jax.random.split(key, 4)
    # weights sized to the padded expert count (dummy experts: never routed
    # to — the router only emits real expert ids — but they make the expert
    # dim mesh-divisible so EP shards instead of replicating)
    e, d, f = cfg.n_experts_total, cfg.d_model, cfg.d_ff
    scale_in = 1.0 / jnp.sqrt(d)
    scale_out = 1.0 / jnp.sqrt(f)
    return {
        # the router only ever emits REAL expert ids
        "router": dense_init(ks[0], d, cfg.n_experts, jnp.float32),
        "w1": (jax.random.normal(ks[1], (e, d, f)) * scale_in).astype(dtype),
        "w3": (jax.random.normal(ks[2], (e, d, f)) * scale_in).astype(dtype),
        "w2": (jax.random.normal(ks[3], (e, f, d)) * scale_out).astype(dtype),
    }


def _init_held(key, cfg, dtype) -> dict:
    ks = jax.random.split(key, 5)
    e, d, f = cfg.n_experts_held, cfg.d_model, cfg.d_ff
    normal = lambda k, shape, fan_in: (
        jax.random.normal(k, shape) / jnp.sqrt(fan_in)).astype(dtype)
    p = {
        # the router spans every expert of the layer, held here or not
        "router": dense_init(ks[0], d, cfg.n_experts, jnp.float32),
        "experts": {"w1": normal(ks[1], (e, d, f), d),
                    "w3": normal(ks[2], (e, d, f), d),
                    "w2": normal(ks[3], (e, f, d), f)},
    }
    if cfg.router_scoring == "sigmoid":
        p["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {"w1": dense_init(k1, d, fs, dtype),
                       "w2": dense_init(k2, fs, d, dtype),
                       "w3": dense_init(k3, d, fs, dtype)}
    return p


def route(p, cfg, x: jnp.ndarray):
    """Each token's top-k experts over all ``n_experts``: (gates [..., k]
    float32, ids [..., k]).  The router's logits in float32; softmax
    scores, or sigmoid scores whose ``router_bias`` steers the choice only
    (DeepSeek-V3's ``noaux_tc`` with one group); the chosen scores
    renormalised when ``norm_topk_prob``, times ``routed_scaling``."""
    # float32 at full precision: a top-k choice turns on small gaps
    logits = jnp.matmul(x.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + p["router_bias"], cfg.top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
    else:
        gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                   cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    if cfg.routed_scaling != 1.0:
        gates = gates * cfg.routed_scaling
    return gates, idx


def _held_dot(spec, x, w, quantize):
    """Every held expert's BitLinear at once, each expert with its own
    gamma: the integer path on a served :class:`TernaryWeight` (gamma
    [E]), else fake-quant (STE) per expert when ``quantize``."""
    if isinstance(w, TernaryWeight):
        xq, xs = quantize_act_int8(x)
        acc = jnp.einsum(spec, xq, w.q, preferred_element_type=jnp.int32)
        if xs.ndim < acc.ndim:          # one scale per token, every expert
            xs = xs[..., None]
        scale = xs.astype(jnp.float32) * w.gamma[:, None]
        return (acc.astype(jnp.float32) * scale).astype(x.dtype)
    if quantize:
        x, w = fake_quant_act(x), jax.vmap(fake_quant_weight)(w)
    return jnp.einsum(spec, x, w)


def held_experts(p, cfg, x: jnp.ndarray):
    """This chip's part of the routed experts' result, dropless.

    Routes every token over all ``n_experts`` and computes the held
    experts ``[expert_offset, expert_offset + experts_held)`` densely for
    every token, each weighted by its gate (zero where the token did not
    choose it): no capacity, so no token is dropped however skewed the
    routing.  Experts held elsewhere add nothing here.  Returns (y [..., d],
    routed [..., experts_held] bool: which held experts each token chose).
    """
    quantize = cfg.quantization == "bitnet"
    with jax.named_scope("moe.router"):
        gates, idx = route(p, cfg, x)
        held = cfg.expert_offset + jnp.arange(cfg.n_experts_held)
        chose = idx[..., None] == held                    # [..., k, held]
        g = jnp.sum(jnp.where(chose, gates[..., None], 0.0), axis=-2)
        routed = jnp.any(chose, axis=-2)
    with jax.named_scope("moe.experts"):
        w = p["experts"]
        h = jax.nn.silu(_held_dot("...d,edf->...ef", x, w["w1"], quantize)
                        ) * _held_dot("...d,edf->...ef", x, w["w3"], quantize)
        out = _held_dot("...ef,efd->...ed", h, w["w2"], quantize)
        y = jnp.einsum("...e,...ed->...d", g, out.astype(jnp.float32))
    return y.astype(x.dtype), routed


def moe_ffn(p, cfg, x: jnp.ndarray):
    """The expert layer's FFN: (y [B, S, d], routed [B, S, held] or None).
    Held experts (dropless) where the config states a share, else
    capacity dispatch; plus the shared expert for every token."""
    routed = None
    if cfg.experts_held:
        y, routed = held_experts(p, cfg, x)
    else:
        y = moe_block(p, cfg, x)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            s = p["shared"]
            y = y + swiglu(x, s["w1"], s["w2"], s["w3"],
                           quantize=cfg.quantization == "bitnet")
    return y, routed


def _expert_dot(spec, x, w, quantize):
    """Per-expert einsum: the integer BitLinear on a served
    :class:`TernaryWeight`, else fake-quant (STE) when ``quantize``."""
    if isinstance(w, TernaryWeight):
        return ternary_dot(x, w, spec)
    if quantize:
        x, w = fake_quant_act(x), fake_quant_weight(w)
    return jnp.einsum(spec, x, w)


def moe_block(p, cfg, x: jnp.ndarray) -> jnp.ndarray:
    """x [B, S, d] -> [B, S, d].  Capacity-dropped top-k routing.

    Routing is **per batch row** (GShard-style grouped capacity): each row
    routes its own S tokens with capacity ``cf * k * S / E``.  Positions
    within an expert come from a per-row cumsum — no cross-device prefix
    sum (a global-T cumsum over the sharded token axis lowers to a chain
    of giant all-reduces), and the dispatch all-to-all happens where it
    should: at the [batch -> expert] buffer boundary.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts_total, cfg.top_k
    cap = int(cfg.capacity_factor * k * s / cfg.n_experts + 1)
    quantize = cfg.quantization == "bitnet"

    gates, idx = route(p, cfg, x)                            # [B, S, k]

    # position of each (token, slot) within its expert, per row
    e_flat = idx.reshape(b, s * k)                           # [B, S*k]
    onehot = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)      # [B, S*k, E]
    cum = jnp.cumsum(onehot, axis=1) - onehot
    pos_in_e = jnp.take_along_axis(
        cum, e_flat[..., None], axis=2
    )[..., 0]                                                # [B, S*k]
    keep = pos_in_e < cap
    slot_pos = jnp.where(keep, pos_in_e, cap - 1)

    # dispatch: buffer [B, E, cap, d].  The scatter is vmapped over the
    # batch row so it lowers with explicit batching dims — GSPMD partitions
    # those along the data axes (a flat 3-D advanced-index scatter would be
    # replicated wholesale, all-reducing [B, S*k, d] per layer).
    x_rep = jnp.repeat(x, k, axis=1)                         # [B, S*k, d]

    def _scatter_row(e_row, p_row, x_row, keep_row):
        buf_row = jnp.zeros((e, cap, d), x.dtype)
        return buf_row.at[e_row, p_row].add(
            jnp.where(keep_row[:, None], x_row, 0)
        )

    buf = jax.vmap(_scatter_row)(e_flat, slot_pos, x_rep, keep)
    # batch stays on the data axes; experts take the model axis (EP) — the
    # constrain boundary is where XLA inserts the dispatch all-to-all
    buf = constrain(buf, "batch", "experts", None, None)

    h = jax.nn.silu(
        _expert_dot("becd,edf->becf", buf, p["w1"], quantize)
    ) * _expert_dot("becd,edf->becf", buf, p["w3"], quantize)
    h = constrain(h, "batch", "experts", None, "ff")
    out_buf = _expert_dot("becf,efd->becd", h, p["w2"], quantize)
    out_buf = constrain(out_buf, "batch", "experts", None, None)

    # combine: gather each (token, slot)'s result, weight by its gate
    # (vmapped per row for the same partitioning reason as the scatter)
    y_slots = jax.vmap(lambda ob, er, pr: ob[er, pr])(
        out_buf, e_flat, slot_pos
    )                                                        # [B, S*k, d]
    y_slots = jnp.where(keep[..., None], y_slots, 0)
    y = (
        y_slots.reshape(b, s, k, d).astype(jnp.float32)
        * gates[..., None]
    ).sum(axis=2)
    return y.astype(x.dtype)


def load_balance_loss(p, cfg, x: jnp.ndarray) -> jnp.ndarray:
    """Switch-style auxiliary loss (mean prob x mean dispatch per expert)."""
    b, s, d = x.shape
    logits = x.reshape(-1, d).astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(probs, cfg.top_k)
    dispatch = jax.nn.one_hot(idx, cfg.n_experts).sum(axis=1)
    return cfg.n_experts * jnp.mean(
        probs.mean(axis=0) * dispatch.mean(axis=0)
    )


def moe_lowering_spec(cfg, *, tokens: int = 16, seed: int = 0):
    """The config's MoE FFN block as a :class:`repro.legion.lowering.MoESpec`
    — the D-Legion workload-zoo view of this model: the router's top-k
    becomes program-level ZTB sparsity (an expert with no routed tokens is
    a fully-sparse window, exactly the analogy documented above)."""
    from repro.legion.lowering import MoESpec

    return MoESpec(
        d_model=cfg.d_model, d_ff=cfg.d_ff, n_experts=cfg.n_experts,
        top_k=cfg.top_k, tokens=tokens, weight_bits=cfg.weight_bits,
        layers=cfg.layers, seed=seed, name=cfg.name,
    )
