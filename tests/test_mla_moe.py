"""Latent attention (MLA), held-expert MoE with sigmoid routing and shared
experts, and a leading dense layer (Moonlight-16B-A3B's block), at a small
size on the CPU, against the plain reference ``bench/references/
moonlight.py`` on seeded random weights.

The program runs in float32 here, as the reference does: the two do the
same arithmetic in another order.  Through 3 layers and the head that
leaves logits of magnitude ~2 within 5e-5 of each other (measured), so the
tolerance is 1e-4; the program in bfloat16 (unit roundoff 2**-8) misses it
by far more than 50 times, which the two whole-model comparisons check.
Where the int8 activation quantizer rounds a value that sits within
float32 noise of a half step, or a router's top-k turns on a gap that
small, program and reference part by far more: the seeded inputs here
stay clear of such ties.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import serve_moe_offline as drv
from bench.references import moonlight as ref
from repro.configs import get_config, reduced
from repro.models import build_model
from repro.models import moe as moe_mod
from repro.models.attention import _mla_absorbed, _mla_expanded
from repro.obs.metrics import MetricsRegistry
from repro.serve import ServeEngine
from repro.serve.engine import prepare_params
from repro.serve.kv_cache import kv_bytes_per_token

SEED = 2 ** 31 + 7
F32 = dict(rtol=0, atol=1e-4)     # float32 order over 3 layers; logits ~ 2


def _sizes(**kw) -> dict:
    """A small Moonlight configuration file: 3 layers (1 dense), hidden
    128, 4 of 8 experts held (offset 4), top-2."""
    cfg = dict(ref_config())
    cfg.update(num_hidden_layers=3, hidden_size=128, num_attention_heads=4,
               num_key_value_heads=4, kv_lora_rank=64, qk_nope_head_dim=32,
               qk_rope_head_dim=16, v_head_dim=32, intermediate_size=256,
               moe_intermediate_size=64, n_routed_experts=8,
               num_experts_per_tok=2, experts_held=4, expert_offset=4,
               vocab_size=512, max_position_embeddings=128)
    cfg.update(kw)
    return cfg


def ref_config() -> dict:
    from bench import harness
    return harness.load_json(harness.BENCH / "configs"
                             / "moonlight-16b-a3b-ep8.json")


@functools.lru_cache(maxsize=None)
def _model(dtype="float32"):
    config = _sizes(torch_dtype=dtype)
    m = ref.sizes(config)
    api = build_model(drv.model_config(config))
    params = drv.program_params(ref.served_weights(m, SEED, jnp.dtype(dtype)))
    return config, m, api, params


@functools.lru_cache(maxsize=None)
def _jitted(dtype="float32"):
    """The small model's prefill, chunk and decode, jitted."""
    api = _model(dtype)[2]
    return (jax.jit(api.prefill), jax.jit(api.prefill_chunk),
            jax.jit(api.decode))


def _ref_logits(m, tokens, dtype="float32"):
    """The reference's logits [n, T, V] of the whole model."""
    return np.stack(ref._per_row(m, SEED, tokens, dtype, ref.identity,
                                 lambda lg, i: np.asarray(lg)))


def _tokens(n=2, t=24):
    return np.random.default_rng(3).integers(1, 512, (n, t)).astype(np.int32)


def test_config_and_counts():
    """The registry's config has the published widths, and the parameter
    counts match the configuration file's memory."""
    cfg = get_config("moonlight-16b-a3b")
    config = ref_config()
    assert (cfg.layers, cfg.d_model, cfg.vocab) == (27, 2048, 163840)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.experts_held, cfg.d_ff,
            cfg.dense_d_ff, cfg.first_k_dense) == (64, 6, 8, 1408, 11264, 1)
    assert cfg.param_count() == config["memory"]["parameters"]
    assert 2 * cfg.param_count() == config["memory"]["parameters_bytes"]
    # one token uses the dense layer, attention, the shared experts and at
    # most top_k of the 8 held experts of each expert layer
    assert cfg.param_count() - cfg.active_param_count() \
        == 2 * 26 * 3 * 2048 * 1408
    assert kv_bytes_per_token(cfg) == config["memory"]["kv_bytes_per_token"]
    kv = get_config("bitnet-1.58b-kv")
    assert 2 * kv.param_count() == 4400419840     # its file's memory


@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m", "qwen3-1.7b"])
def test_counts_of_other_configs_keep_their_formula(arch):
    """Held experts, MLA and leading dense layers leave the other configs'
    counts as the plain formula gives them."""
    cfg = get_config(arch)
    d, hd = cfg.d_model, cfg.head_dim_
    attn = d * cfg.n_heads * hd * 2 + 2 * d * cfg.kv_heads * hd
    ffn = (cfg.n_experts * (3 * d * cfg.d_ff + d) if cfg.n_experts
           else 3 * d * cfg.d_ff)
    want = cfg.vocab * d * (1 + (not cfg.tie_embeddings)) + d \
        + cfg.layers * (attn + 2 * d + ffn)
    assert cfg.param_count() == want


def test_prefill_then_decode_matches_reference():
    """Whole-prompt prefill, then decode through the latent cache (the
    absorbed form) on the served int8 codes, teacher-forced: every logit
    within float32 order of the reference's full forward; in bfloat16 the
    same run misses by far."""
    toks = _tokens(1, 20)
    want = _ref_logits(_model()[1], toks)

    def served(dtype):
        _, _, api, params = _model(dtype)
        prefill, _, decode = _jitted(dtype)
        params = prepare_params(params)
        cache = api.init_cache(1, 32)
        lg, cache = prefill(params, {"tokens": jnp.asarray(toks[:, :12])},
                            cache)
        rows = [lg[0, -1]]
        for p in range(12, 20):
            lg, cache, _ = decode(params, jnp.asarray(toks[:, p]), cache,
                                  jnp.asarray([p], jnp.int32))
            rows.append(lg[0, -1])
        return np.asarray(jnp.stack(rows), np.float32)

    got = served("float32")
    np.testing.assert_allclose(got[:-1], want[0, 11:19], **F32)
    assert np.abs(served("bfloat16")[:-1] - want[0, 11:19]).max() \
        > 50 * F32["atol"]


def test_chunked_prefill_matches_one_prefill():
    """Chunks of 5/4/3 (absorbed attention over the staged cache) against
    one prefill of the 12 tokens (expanded): the last logits, every cache
    leaf and the decode continuation, within float32 order."""
    _, _, api, params = _model()
    prefill, chunk, decode = _jitted()
    params = prepare_params(params)
    prompt = jnp.asarray(_tokens(1, 12))
    lg_whole, whole = prefill(params, {"tokens": prompt},
                              api.init_cache(1, 32))
    cache, pos0 = api.init_cache(1, 32), 0
    for c in (5, 4, 3):
        lg, cache = chunk(params, prompt[:, pos0:pos0 + c], cache, pos0)
        pos0 += c
    np.testing.assert_allclose(lg[:, -1], lg_whole[:, -1], **F32)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(whole)):
        np.testing.assert_allclose(a, b, **F32)
    tok = jnp.argmax(lg_whole[:, -1], axis=-1).astype(jnp.int32)
    pos = jnp.asarray([12], jnp.int32)
    lg_a = decode(params, tok, whole, pos)[0]
    lg_b = decode(params, tok, cache, pos)[0]
    np.testing.assert_allclose(lg_a, lg_b, **F32)


@pytest.mark.parametrize("served", [True, False])
def test_absorbed_matches_expanded(served):
    """The cache's absorbed form and the expanded form of the same
    attention: kv_b_proj's BitLinear quantizes the latent per position, so
    folding its halves into the query and the output changes only the
    order of the sums (served int8 codes, or per-step fake-quant)."""
    config, m, api, params = _model()
    cfg = api.cfg
    if served:
        params = prepare_params(params)
    p = jax.tree.map(lambda a: a[0], params["blocks"]["attn"])
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    b, s, h = 2, 9, cfg.n_heads
    q_nope = jax.random.normal(k[0], (b, s, h, cfg.qk_nope_head_dim))
    q_pe = jax.random.normal(k[1], (b, s, h, cfg.qk_rope_head_dim))
    c = jax.random.normal(k[2], (b, s, cfg.kv_lora_rank))
    k_pe = jax.random.normal(k[3], (b, s, cfg.qk_rope_head_dim))
    want = _mla_expanded(p, cfg, q_nope, q_pe, c, k_pe, True)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    got = _mla_absorbed(p, cfg, q_nope, q_pe, c, k_pe, pos, True)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


def _held_layer(config, offset, held, w=None):
    """Layer 1 of the reference's weights (or ``w``) as a program expert
    layer holding ``held`` experts from ``offset``."""
    config = dict(config, expert_offset=offset, experts_held=held)
    m = ref.sizes(config)
    if w is None:
        w = ref.layer_weights(m, SEED, 1, "float32")
    p = {"router": w["router"], "router_bias": w["router_bias"],
         "experts": {k: w["expert_" + k] for k in ("w1", "w2", "w3")},
         "shared": {k: w["shared_" + k] for k in ("w1", "w2", "w3")}}
    return drv.model_config(config), m, w, p


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips of 8 experts each (offsets 0, 8, ..., 56) over a
    64-expert top-6 layer: the held experts' parts of all eight shares,
    with the shared expert (computed alike on every chip) counted once,
    add up to the uncut layer, in the program and in the reference.  A
    share holds the uncut layer's experts, as the reference makes them."""
    config = _sizes(n_routed_experts=64, num_experts_per_tok=6)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 128))
    cfg, m, w, p = _held_layer(config, 0, 64)
    whole, routed = moe_mod.moe_ffn(p, cfg, x)
    assert np.all(np.asarray(routed).sum(-1) == 6)
    shared = whole - moe_mod.held_experts(p, cfg, x)[0]
    share8 = _held_layer(config, 8, 8)[2]
    for k in ("w1", "w2", "w3"):
        np.testing.assert_array_equal(share8["expert_" + k],
                                      w["expert_" + k][8:16])
    parts, ref_parts = 0.0, 0.0
    for offset in range(0, 64, 8):
        w_i = dict(w, **{"expert_" + k: w["expert_" + k][offset:offset + 8]
                         for k in ("w1", "w2", "w3")})
        cfg_i, m_i, _, p_i = _held_layer(config, offset, 8, w_i)
        y, r = moe_mod.held_experts(p_i, cfg_i, x)
        assert r.shape == (2, 16, 8)
        parts = parts + y
        ref_parts = ref_parts + ref._experts(m_i, ref.identity, w_i, x)
    np.testing.assert_allclose(parts + shared, whole, rtol=0, atol=1e-5)
    np.testing.assert_allclose(ref_parts, whole - shared, rtol=0, atol=1e-5)


def test_dropless_when_every_token_picks_one_expert():
    """A correction bias that sends every token to held expert 5: all 64
    tokens of one row get its output at their gate, none dropped (the
    capacity dispatch keeps cf * k * S / E + 1 of them)."""
    config = _sizes()
    cfg, m, w, p = _held_layer(config, 4, 4)
    p = dict(p, router_bias=p["router_bias"].at[5].set(100.0))
    del p["shared"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, 128))
    y, routed = moe_mod.moe_ffn(p, cfg, x)
    assert np.all(np.asarray(routed)[..., 1])        # expert 5 = held 1
    gates, _ = ref.route(m, x, w["router"], w["router_bias"].at[5].set(100.))
    one = ref._swiglu(x, w["expert_w1"][1], w["expert_w2"][1],
                      w["expert_w3"][1], m["quant_eps"], ref.identity)
    want = ref._experts(m, ref.identity,
                        dict(w, router_bias=w["router_bias"].at[5].set(100.)),
                        x)
    np.testing.assert_allclose(y, want, rtol=0, atol=1e-5)
    # every token's expert-5 part is there, at its gate
    assert np.all(np.asarray(gates)[..., 1] > 0)
    other = want - gates[..., 1:2] * one
    np.testing.assert_allclose(y - gates[..., 1:2] * one, other, atol=1e-5)
    capped = int(cfg.capacity_factor * cfg.top_k * 64 / cfg.n_experts + 1)
    assert capped < 64


def test_sigmoid_routing():
    """Selection by score plus bias, gates the unbiased scores,
    renormalised and scaled; the bias never enters a gate."""
    cfg = reduced(get_config("moonlight-16b-a3b"))
    logits = jnp.asarray([[0.0, 1.0, -1.0, 0.5, 2.0, -2.0, 0.2, 0.1]])
    x = jnp.eye(8)[None, :1, :]            # token whose logits are row 0
    router = jnp.zeros((8, 8)).at[0].set(logits[0])
    bias = jnp.zeros(8).at[2].set(5.0)     # expert 2 wins on bias alone
    p = {"router": router, "router_bias": bias}
    gates, idx = moe_mod.route(p, cfg.replace(d_model=8), x)
    assert set(np.asarray(idx).ravel()) == {2, 4}
    s = jax.nn.sigmoid(logits[0])
    want = np.array([s[2], s[4]]) / (s[2] + s[4]) * cfg.routed_scaling
    got = dict(zip(np.asarray(idx).ravel(), np.asarray(gates).ravel()))
    np.testing.assert_allclose([got[2], got[4]], want, rtol=1e-6)
    softmax = moe_mod.route({"router": router}, cfg.replace(
        d_model=8, router_scoring="softmax", routed_scaling=1.0), x)
    assert float(softmax[0].sum()) == pytest.approx(1.0)


def test_leading_dense_layer():
    """Layer 0 is a SwiGLU of the dense width under ``dense_blocks``, the
    rest expert layers under ``blocks``; the whole model's logits match the
    reference's (each kind of layer in its place)."""
    _, m, api, params = _model()
    assert params["dense_blocks"]["mlp"]["w1"].shape == (1, 128, 256)
    assert params["blocks"]["moe"]["experts"]["w1"].shape == (2, 4, 128, 64)
    assert "moe" not in params["dense_blocks"]
    toks = _tokens()
    got = np.asarray(jax.jit(api.train_logits)(
        params, {"tokens": jnp.asarray(toks)}))
    np.testing.assert_allclose(got, _ref_logits(m, toks), **F32)
    _, _, api16, p16 = _model("bfloat16")
    low = np.asarray(jax.jit(api16.train_logits)(
        p16, {"tokens": jnp.asarray(toks)}), np.float32)
    assert np.abs(low - _ref_logits(m, toks, "bfloat16")).max() \
        > 50 * F32["atol"]


def test_served_codes_match_per_step_fake_quant():
    """The engine's int8 codes, made once (per expert for the routed
    experts), serve the logits the latent weights give with per-step
    fake-quant."""
    _, _, api, params = _model()
    prepared = prepare_params(params)
    experts = prepared["blocks"]["moe"]["experts"]["w1"]
    assert experts.gamma.shape == (2, 4)         # layer x held expert
    assert prepared["blocks"]["moe"]["router"].dtype == jnp.float32
    assert not hasattr(prepared["lm_head"], "gamma")
    toks = jnp.asarray(_tokens(2, 10))
    pos = jnp.asarray([10, 10], jnp.int32)
    prefill, _, decode = _jitted()
    outs = []
    for p in (params, prepared):
        _, cache = prefill(p, {"tokens": toks}, api.init_cache(2, 16))
        outs.append(decode(p, toks[:, -1], cache, pos))
    np.testing.assert_allclose(outs[1][0], outs[0][0], **F32)
    np.testing.assert_array_equal(outs[1][2], outs[0][2])


def test_engine_counts_routed_tokens():
    """The decode brings each slot's held-expert choices back with its
    tokens; the engine adds the active slots' to each step event and to
    the ``serve_moe_*`` counters, and serves 17 ternary leaves."""
    _, _, api, params = _model()
    metrics = MetricsRegistry()
    eng = ServeEngine(api, params, max_slots=3, max_seq=48,
                      prefill_chunk_tokens=8, metrics=metrics)
    assert eng.ternary_projection_leaves == 17
    events = []
    eng.step_observers.append(events.append)
    for n in (9, 5):
        eng.submit(np.arange(1, n + 1), max_new_tokens=6)
    eng.run_until_done()
    decoded = [e for e in events if e["uids"]]
    assert decoded and all("moe_routed" in e for e in decoded)
    for e in decoded:
        # 2 expert layers, top-2 of 8 experts, 4 held
        assert e["moe_experts_hit"] <= min(2 * 4, e["moe_routed"])
        assert e["moe_routed"] <= 2 * 2 * len(e["uids"])
    snap = metrics.snapshot()
    assert snap["serve_moe_routed_tokens"]["series"][""] == sum(
        e["moe_routed"] for e in decoded)
    assert snap["serve_moe_experts_hit"]["series"][""] == sum(
        e["moe_experts_hit"] for e in decoded)


def test_engine_counts_match_a_recount():
    """The counts are the routing of the active slots' tokens alone: a
    decode of two active slots among three, recounted from the decode's
    own output (the idle slot's token routes too, and is not counted)."""
    _, _, api, params = _model()
    eng = ServeEngine(api, params, max_slots=3, max_seq=48)
    seen = []
    decode = eng._decode

    def spy(*args):
        out = decode(*args)
        # the donating decode consumes the cache it is given
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(args[2]))
        seen.append(np.asarray(out[2]))
        return out

    eng._decode = spy
    events = []
    eng.step_observers.append(events.append)
    eng.submit(np.arange(1, 7), max_new_tokens=3)
    eng.submit(np.arange(3, 8), max_new_tokens=3)
    eng.step()                  # prefill both, then one decode of the two
    (ev,) = [e for e in events if e["kind"] == "decode"]
    (routed,) = seen
    assert routed.shape == (3, 2, 4)       # slots, expert layers, held
    active = routed[:2].reshape(2, -1)
    assert ev["moe_routed"] == int(active.sum())
    assert ev["moe_experts_hit"] == int(active.any(axis=0).sum())


def test_named_scopes_in_compiled_decode():
    """The compiled decode carries the ``mla``, ``moe.router``,
    ``moe.experts`` and ``moe.shared`` scopes in its op names."""
    _, _, api, params = _model()
    params = prepare_params(params)
    cache = api.init_cache(2, 16)
    hlo = jax.jit(api.decode).lower(
        params, jnp.zeros((2,), jnp.int32), cache,
        jnp.zeros((2,), jnp.int32)).compile().as_text()
    for scope in ("mla", "moe.router", "moe.experts", "moe.shared"):
        assert f"/{scope}/" in hlo, scope
