"""Token-choice top-k MoE with capacity-based dispatch (expert parallelism).

Experts shard over the "model" mesh axis (EP) — each expert is a Legion-like
independent worker; tokens route via scatter/gather, which XLA SPMD turns
into the expected all-to-all pattern.  The ZTB analogy: an expert with no
routed tokens is a fully-sparse window — XLA still executes the (empty)
GEMM, but the simulator and sparse serving path skip it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models.common import dense_init
from repro.quant.bitnet import (
    TernaryWeight,
    fake_quant_act,
    fake_quant_weight,
    ternary_dot,
)


def init_moe_params(key, cfg, dtype) -> dict:
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

    logits = x.astype(jnp.float32) @ p["router"]             # [B, S, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                     # [B, S, k]
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

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
