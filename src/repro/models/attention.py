"""Multi-head attention with GQA/MQA, RoPE, qk-norm and KV caching, and
multi-head latent attention (MLA, DeepSeek-V2/V3) over a latent cache.

Head-over-"model"-axis sharding mirrors the paper's head-per-Legion mapping;
replicated KV (kv_heads < model-axis size) mirrors the KV multicast.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models.common import (
    apply_rope,
    bitlinear_codes,
    bitlinear_weight,
    dense,
    dense_init,
    rms_norm,
    rope_angles,
)


class KVCache(NamedTuple):
    """K and V per head, or for MLA the latent and the rotary key."""

    k: jnp.ndarray   # [B, Hkv, S_max, hd]; MLA: latent c [B, 1, S_max, r]
    v: jnp.ndarray   # [B, Hkv, S_max, hd]; MLA: roped key [B, 1, S_max, rope]


def init_attn_params(key, cfg, dtype) -> dict:
    hd = cfg.head_dim_
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.kv_heads * hd, dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(p, cfg, x, positions):
    b, s, _ = x.shape
    hd = cfg.head_dim_
    quant = cfg.quantization == "bitnet"
    q = dense(x, p["wq"], quantize=quant).reshape(b, s, cfg.n_heads, hd)
    k = dense(x, p["wk"], quantize=quant).reshape(b, s, cfg.kv_heads, hd)
    v = dense(x, p["wv"], quantize=quant).reshape(b, s, cfg.kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    cos, sin = rope_angles(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def _tile_scores(qb, kb, qi, ki, bq, bk, causal, scale, q_offset=0):
    """[b,hkv,g,bq,bk] masked scaled scores for one (q-block, kv-block).

    ``q_offset`` shifts global query positions (context parallelism: each
    seq shard masks against its true positions)."""
    sc = jnp.einsum("bqkgd,btkd->bkgqt", qb, kb) * scale
    if causal:
        qpos = q_offset + qi * bq + jnp.arange(bq)[:, None]
        kpos = ki * bk + jnp.arange(bk)[None, :]
        # barrier: stops XLA hoisting the (broadcast) mask out of the tile
        # loops, which would materialize [b,h,nk,bq,bk] pred buffers
        mask = jax.lax.optimization_barrier(qpos >= kpos)
        sc = jnp.where(mask[None, None, None], sc, -1e30)
    return sc


def _flash_fwd_impl(q, k, v, q_offset, causal, bq, bk):
    b, s, h, hd = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    g = h // hkv
    nq, nk = s // bq, t // bk
    scale = 1.0 / (hd ** 0.5)
    qt = q.reshape(b, nq, bq, hkv, g, hd).astype(jnp.float32)
    kt = k.reshape(b, nk, bk, hkv, hd).astype(jnp.float32)
    vt = v.reshape(b, nk, bk, hkv, hd).astype(jnp.float32)

    def q_block(_, qi):
        qb = qt[:, qi]                                   # [b,bq,hkv,g,hd]

        def kv_block(state, ki):
            m, l, acc = state
            sc = _tile_scores(qb, kt[:, ki], qi, ki, bq, bk, causal, scale,
                              q_offset)
            m_new = jnp.maximum(m, sc.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p, vt[:, ki]
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, bq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, bq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_block, (m0, l0, a0),
                                      jnp.arange(nk))
        l = jnp.maximum(l, 1e-30)
        out = acc / l[..., None]                         # [b,hkv,g,bq,hd]
        lse = m + jnp.log(l)                             # [b,hkv,g,bq]
        return None, (out.transpose(0, 3, 1, 2, 4), lse)

    _, (blocks, lses) = jax.lax.scan(q_block, None, jnp.arange(nq))
    out = blocks.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h, hd)
    # lses [nq, b, hkv, g, bq] -> [b, hkv, g, s]
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(b, hkv, g, s)
    return out.astype(q.dtype), lse


def _flash_bwd_impl(causal, bq, bk, res, dout):
    """O(S)-memory flash backward: per-tile recompute of p from saved lse."""
    q, k, v, q_offset, out, lse = res
    b, s, h, hd = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    g = h // hkv
    nq, nk = s // bq, t // bk
    scale = 1.0 / (hd ** 0.5)
    f32 = jnp.float32
    qt = q.reshape(b, nq, bq, hkv, g, hd).astype(f32)
    kt = k.reshape(b, nk, bk, hkv, hd).astype(f32)
    vt = v.reshape(b, nk, bk, hkv, hd).astype(f32)
    dot = dout.reshape(b, nq, bq, hkv, g, hd).astype(f32)
    # D_i = rowsum(dout * out)
    dmat = (dout.astype(f32) * out.astype(f32)).sum(-1)   # [b,s,h]
    dmat = dmat.reshape(b, nq, bq, hkv, g).transpose(0, 3, 4, 1, 2)
    lset = lse.reshape(b, hkv, g, nq, bq)

    def q_block(carry, qi):
        dk_acc, dv_acc = carry                 # [b,nk,bk,hkv,hd] each
        qb = qt[:, qi]
        dob = dot[:, qi]                       # [b,bq,hkv,g,hd]
        lse_i = lset[:, :, :, qi]              # [b,hkv,g,bq]
        d_i = dmat[:, :, :, qi]                # [b,hkv,g,bq]

        def kv_block(state, ki):
            dq_b, dk_acc, dv_acc = state
            sc = _tile_scores(qb, kt[:, ki], qi, ki, bq, bk, causal, scale,
                              q_offset)
            p = jnp.exp(sc - lse_i[..., None])            # [b,hkv,g,bq,bk]
            dv_tile = jnp.einsum("bkgqt,bqkgd->btkd", p, dob)
            dp = jnp.einsum("bqkgd,btkd->bkgqt", dob, vt[:, ki])
            ds = p * (dp - d_i[..., None]) * scale
            dq_b = dq_b + jnp.einsum("bkgqt,btkd->bqkgd", ds, kt[:, ki])
            dk_tile = jnp.einsum("bkgqt,bqkgd->btkd", ds, qb)
            dk_acc = dk_acc.at[:, ki].add(dk_tile)
            dv_acc = dv_acc.at[:, ki].add(dv_tile)
            return (dq_b, dk_acc, dv_acc), None

        dq0 = jnp.zeros((b, bq, hkv, g, hd), f32)
        (dq_b, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_block, (dq0, dk_acc, dv_acc), jnp.arange(nk)
        )
        return (dk_acc, dv_acc), dq_b

    dk0 = jnp.zeros((b, nk, bk, hkv, hd), f32)
    dv0 = jnp.zeros((b, nk, bk, hkv, hd), f32)
    (dk, dv), dqs = jax.lax.scan(q_block, (dk0, dv0), jnp.arange(nq))
    dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h, hd)
    return (
        dq.astype(q.dtype),
        dk.reshape(b, t, hkv, hd).astype(k.dtype),
        dv.reshape(b, t, hkv, hd).astype(v.dtype),
        None,   # q_offset (int): no cotangent
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, q_offset, causal, bq, bk):
    return _flash_fwd_impl(q, k, v, q_offset, causal, bq, bk)[0]


def _flash_fwd(q, k, v, q_offset, causal, bq, bk):
    out, lse = _flash_fwd_impl(q, k, v, q_offset, causal, bq, bk)
    return out, (q, k, v, q_offset, out, lse)


_flash.defvjp(_flash_fwd, _flash_bwd_impl)


def _flash_ref(q, k, v, *, causal: bool, bq: int = 512, bk: int = 256,
               q_offset=0):
    """Double-chunked online-softmax attention (custom_vjp: O(S) memory in
    forward AND backward — per-tile recompute, saves only out + lse).

    This is the XLA-path twin of kernels/flash_attention — required for the
    32k prefill / 4k train cells to fit HBM.
    q [B,S,H,hd]; k/v [B,T,Hkv,hd].
    """
    s, t = q.shape[1], k.shape[1]
    bq = min(bq, s)
    bk = min(bk, t)
    return _flash(q, k, v, q_offset, causal, bq, bk)


def _context_parallel_flash(q, k, v, *, causal: bool, rules):
    """Context parallelism: queries shard over the "model" axis (their seq
    dim), K/V replicate — the paper's KV multicast as a shard_map.  Each
    shard runs a *local* flash over its query slice with globally-correct
    causal masking via the position offset."""
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    seq_ax = rules.table["seq"]
    b_ax = rules.table["batch"]
    s = q.shape[1]
    msize = mesh.shape[seq_ax] if isinstance(seq_ax, str) else 1
    s_local = s // msize

    def local(qs, ks, vs):
        off = jax.lax.axis_index(seq_ax) * s_local
        bq = min(512, s_local)
        bk = min(256, ks.shape[1])
        return _flash(qs, ks, vs, off, causal, bq, bk)

    from repro.compat import shard_map

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(b_ax, seq_ax, None, None), P(b_ax, None, None, None),
                  P(b_ax, None, None, None)),
        out_specs=P(b_ax, seq_ax, None, None),
        check_vma=False,
    )(q, k, v)


# Sequences at or below this length use the plain einsum path (cheaper to
# compile, fine for smoke tests); longer ones use the chunked flash path.
FLASH_THRESHOLD = 2048


def _sdpa(q, k, v, *, causal: bool, q_offset=None, kv_len: Optional[int] = None):
    """q [B,S,H,hd], k/v [B,T,Hkv,hd] — einsum attention, GQA via reshape."""
    if (q.shape[1] > FLASH_THRESHOLD and q.shape[1] == k.shape[1]
            and kv_len is None and v.shape[-1] == q.shape[-1]
            and q.shape[1] % 1024 == 0 and k.shape[1] % 512 == 0):
        from repro.distributed.sharding import active_rules
        rules = active_rules()
        if rules is not None and rules.table.get("seq") is not None:
            seq_ax = rules.table["seq"]
            msize = rules.mesh.shape.get(seq_ax, 1)
            if q.shape[1] % (msize * 128) == 0:
                return _context_parallel_flash(q, k, v, causal=causal,
                                               rules=rules)
        return _flash_ref(q, k, v, causal=causal)
    b, s, h, hd = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, s, hkv, g, hd)
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) / (hd ** 0.5)
    if causal:
        qpos = jnp.arange(s)[:, None] + (q_offset if q_offset is not None
                                         else 0)
        kpos = jnp.arange(t)[None, :]
        mask = qpos >= kpos
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    elif kv_len is not None:
        kpos = jnp.arange(t)
        if jnp.ndim(kv_len) == 0:
            mask = (kpos < kv_len)[None, None, None, None, :]
        else:  # per-slot [B,1,1,1,1] lengths (continuous batching)
            mask = kpos[None, None, None, None, :] < kv_len
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h, v.shape[-1]).astype(q.dtype)


def write_rows(leaf, rows, pos, layer=None):
    """Write ``rows`` [B, s, H, w] into a cache leaf [B, H, S_max, w] at
    sequence positions [pos, pos + s) and return the updated leaf.

    ``pos`` is one scalar for every slot (a lockstep batch, a prefill
    chunk: one ``dynamic_update_slice``) or one position per slot [B]
    (continuous batching: one scatter of unique [w] rows, one per slot,
    head and position; a row out of range is dropped).  Scattering whole
    [H, w] windows instead made the TPU compiler hold the cache in another
    layout and copy all of it in and out of every step.  With ``layer``,
    ``leaf`` is the stacked [L, B, H, S_max, w] cache and the rows land in
    that layer, so a cache carried through the layer scan is updated in
    place."""
    lead = () if layer is None else (layer,)
    if jnp.ndim(pos) == 0:
        block = rows.transpose(0, 2, 1, 3)
        block = block.reshape((1,) * len(lead) + block.shape)
        return jax.lax.dynamic_update_slice(leaf, block,
                                            lead + (0, 0, pos, 0))
    b, s, h = rows.shape[:3]
    at = (pos[:, None] + jnp.arange(s))[:, None, :]           # [B, 1, s]
    slots = jnp.arange(b)[:, None, None]
    heads = jnp.arange(h)[None, :, None]
    return leaf.at[lead + (slots, heads, at)].set(
        rows.transpose(0, 2, 1, 3), unique_indices=True, mode="drop")


def layer_lanes(leaf, layer=None):
    """Layer ``layer``'s [B, H, S_max, w] lanes of a stacked cache leaf;
    without ``layer``, the leaf itself."""
    if layer is None:
        return leaf
    return jax.lax.dynamic_index_in_dim(leaf, layer, keepdims=False)


def attention(
    p: dict, cfg, x: jnp.ndarray, *, positions: jnp.ndarray,
    cache: Optional[KVCache] = None, cache_pos=None, layer=None,
) -> tuple:
    """Full attention sub-layer.

    Training: ``cache=None``.  Prefill: a cache to fill at [0, S).
    Decode or prefill chunk: ``cache_pos``, the write position, a scalar
    (lockstep batch, chunk) or per slot [B] (continuous batching); the
    step's K/V rows are written there and the step attends the cache.
    ``layer`` indexes a stacked cache (see :func:`write_rows`).
    Returns (out [B, S, d], new_cache).
    """
    b, s, _ = x.shape
    quant = cfg.quantization == "bitnet"
    q, k, v = _project_qkv(p, cfg, x, positions)
    # under context parallelism "seq" carries the model axis and heads are
    # local; otherwise heads take the model axis (head-per-Legion mapping)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)

    new_cache = None
    if cache is not None:
        at = 0 if cache_pos is None else cache_pos
        new_cache = KVCache(write_rows(cache.k, k, at, layer),
                            write_rows(cache.v, v, at, layer))
    if cache_pos is None or cache is None:
        # training, or a prefill attending the prompt itself
        out = _sdpa(q, k, v, causal=cfg.causal)
    else:
        kt = layer_lanes(new_cache.k, layer).transpose(0, 2, 1, 3)
        vt = layer_lanes(new_cache.v, layer).transpose(0, 2, 1, 3)
        if s > 1 and jnp.ndim(cache_pos) == 0:
            # prefill chunk staged at [cache_pos, cache_pos + s): causal
            # masking against global positions — earlier chunks already
            # sit in the cache below cache_pos, later rows mask out.
            out = _sdpa(q, kt, vt, causal=True, q_offset=cache_pos)
        else:
            kv_len = cache_pos + 1
            if jnp.ndim(cache_pos) == 1:
                kv_len = kv_len[:, None, None, None, None]
            out = _sdpa(q, kt, vt, causal=False, kv_len=kv_len)

    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim_)
    return dense(out, p["wo"], quantize=quant), new_cache


def init_kv_cache(cfg, batch: int, max_seq: int, dtype) -> KVCache:
    if cfg.kv_lora_rank:
        return KVCache(
            jnp.zeros((batch, 1, max_seq, cfg.kv_lora_rank), dtype),
            jnp.zeros((batch, 1, max_seq, cfg.qk_rope_head_dim), dtype))
    shape = (batch, cfg.kv_heads, max_seq, cfg.head_dim_)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


# --------------------------------------------------------------------------- #
# Multi-head latent attention (MLA)
# --------------------------------------------------------------------------- #

def init_mla_params(key, cfg, dtype) -> dict:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, h * (nope + rope), dtype),
        "wkv_a": dense_init(ks[1], d, r + rope, dtype),
        "kv_norm": jnp.ones((r,), dtype),
        "wkv_b": dense_init(ks[2], r, h * (nope + vd), dtype),
        "wo": dense_init(ks[3], h * vd, d, dtype),
    }


def _mla_expanded(p, cfg, q_nope, q_pe, c, k_pe, quant):
    """Attention over the sequence's own positions: ``kv_b_proj(c)`` gives
    each head its K_nope and V; the rotary key is shared by the heads."""
    b, s, h, _ = q_nope.shape
    nope = cfg.qk_nope_head_dim
    kv = dense(c, p["wkv_b"], quantize=quant).reshape(b, s, h, -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_pe[:, :, None], (b, s, h, k_pe.shape[-1]))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    return _sdpa(q, k, kv[..., nope:], causal=cfg.causal)


def _mla_absorbed(p, cfg, q_nope, q_pe, c_all, pe_all, positions, quant):
    """Attention over the cached latent itself: ``kv_b_proj``'s K half is
    folded into each query and its V half into each head's output, so no
    head's K or V is ever formed.  Query positions ``positions`` [B, s]
    attend every cached position at or below them."""
    h, nope = cfg.n_heads, cfg.qk_nope_head_dim
    f32 = jnp.float32
    w = bitlinear_weight(p["wkv_b"], quant).reshape(cfg.kv_lora_rank, h, -1)
    # the latent as kv_b's BitLinear multiplies it: integer codes and one
    # scale per position
    cq, cs = bitlinear_codes(c_all, p["wkv_b"], quant)        # [B,T,r] [B,T,1]
    cs = cs[..., 0][:, None, None, :]                          # [B,1,1,T]
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope.astype(f32), w[..., :nope])
    sc = jnp.einsum("bshr,btr->bhst", q_lat, cq) * cs + jnp.einsum(
        "bshe,bte->bhst", q_pe.astype(f32), pe_all.astype(f32))
    sc = sc / ((nope + cfg.qk_rope_head_dim) ** 0.5)
    t = c_all.shape[1]
    mask = jnp.arange(t)[None, None, :] <= positions[:, :, None]   # [B,s,T]
    probs = jax.nn.softmax(jnp.where(mask[:, None], sc, -1e30), axis=-1)
    o_lat = jnp.einsum("bhst,btr->bshr", probs * cs, cq)
    out = jnp.einsum("bshr,rhd->bshd", o_lat, w[..., nope:])
    return out.astype(q_nope.dtype)


def mla_attention(
    p: dict, cfg, x: jnp.ndarray, *, positions: jnp.ndarray,
    cache: Optional[KVCache] = None, cache_pos=None, layer=None,
) -> tuple:
    """Multi-head latent attention sub-layer (DeepSeek-V3, ``q_lora_rank``
    null: a plain ``q_proj``).

    ``[c, k_pe] = kv_a_proj_with_mqa(x)``, ``c`` normalised by
    ``kv_a_layernorm``; the cache holds ``c`` and the roped ``k_pe``.
    Scores scale by ``1/sqrt(qk_nope + qk_rope)``.  Rotary positions are
    rotate-half on the rope dims (HF's interleaved layout is the same up
    to a fixed permutation of the rope weights).

    Two forms of one arithmetic: attention over the sequence's own
    positions (training, a whole-prompt prefill) expands ``kv_b_proj(c)``;
    attention over the cache (a decode step, a prefill chunk) absorbs
    ``kv_b_proj`` into the query and the output and reads the latent.
    ``kv_b_proj``'s BitLinear quantizes ``c`` per position, so the two
    agree up to summation order.  ``cache``, ``cache_pos`` and ``layer``
    as :func:`attention` takes them.  Returns (out [B, s, d], new_cache).
    """
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    nope = cfg.qk_nope_head_dim
    quant = cfg.quantization == "bitnet"
    with jax.named_scope("mla"):
        q = dense(x, p["wq"], quantize=quant).reshape(b, s, h, -1)
        kv = dense(x, p["wkv_a"], quantize=quant)
        c = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)  # [B, s, r]
        cos, sin = rope_angles(positions, cfg.qk_rope_head_dim,
                               cfg.rope_theta)
        q_nope, q_pe = q[..., :nope], apply_rope(q[..., nope:], cos, sin)
        k_pe = apply_rope(kv[..., None, r:], cos, sin)[:, :, 0]  # [B, s, e]
        new_cache = None
        if cache is not None:
            at = 0 if cache_pos is None else cache_pos
            new_cache = KVCache(write_rows(cache.k, c[:, :, None], at, layer),
                                write_rows(cache.v, k_pe[:, :, None], at,
                                           layer))
        if cache_pos is None or cache is None:
            # training, or a whole-prompt prefill attending the prompt
            out = _mla_expanded(p, cfg, q_nope, q_pe, c, k_pe, quant)
        else:
            out = _mla_absorbed(p, cfg, q_nope, q_pe,
                                layer_lanes(new_cache.k, layer)[:, 0],
                                layer_lanes(new_cache.v, layer)[:, 0],
                                positions, quant)
        out = out.reshape(b, s, h * cfg.v_head_dim)
        return dense(out, p["wo"], quantize=quant), new_cache
