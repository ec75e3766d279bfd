"""Data pipeline + serving engine."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.data import (
    Prefetcher,
    TokenShardReader,
    synthetic_batch,
    write_token_shard,
)
from repro.models import build_model
from repro.serve import Request, ServeEngine
from repro.serve.engine import prepare_params
from repro.serve.kv_cache import kv_bytes_per_token, plan


def test_synthetic_batch_deterministic():
    cfg = reduced(get_config("qwen3-1.7b"))
    b1 = synthetic_batch(cfg, batch=4, seq=32, step=7)
    b2 = synthetic_batch(cfg, batch=4, seq=32, step=7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = synthetic_batch(cfg, batch=4, seq=32, step=8)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # next-token objective: targets are tokens shifted by one
    full = synthetic_batch(cfg, batch=4, seq=32, step=7)
    assert (full["targets"][:, :-1] == full["tokens"][:, 1:]).all()


def test_token_shard_reader_host_split(tmp_path):
    path = str(tmp_path / "shard.bin")
    rng = np.random.default_rng(0)
    write_token_shard(path, rng.integers(0, 1000, 100_000))
    reader = TokenShardReader(path, vocab=1000)
    full = reader.batch(batch=8, seq=64, step=3)
    h0 = reader.batch(batch=8, seq=64, step=3, host=0, num_hosts=2)
    h1 = reader.batch(batch=8, seq=64, step=3, host=1, num_hosts=2)
    np.testing.assert_array_equal(
        np.concatenate([h0["tokens"], h1["tokens"]]), full["tokens"]
    )
    assert (full["targets"][:, :-1] == full["tokens"][:, 1:]).all()


def test_prefetcher_order():
    pf = Prefetcher(lambda s: {"step": np.array([s])}, depth=2)
    steps = [s for s, _ in pf(5, 12)]
    assert steps == list(range(5, 12))


@pytest.mark.parametrize("arch", ["smollm-360m", "mamba2-130m", "zamba2-7b"])
def test_serve_engine_continuous_batching(arch):
    cfg = reduced(get_config(arch))
    api = build_model(cfg)
    params = prepare_params(api.init(jax.random.PRNGKey(0)))
    eng = ServeEngine(api, params, max_slots=3, max_seq=96)
    reqs = [eng.submit(np.arange(1, 4 + i), max_new_tokens=4 + i % 3)
            for i in range(5)]
    done = eng.run_until_done()
    assert len(done) == 5
    for r in done:
        assert len(r.output) == r.max_new_tokens
        assert all(0 <= t < cfg.vocab for t in r.output)


def test_serve_greedy_deterministic():
    cfg = reduced(get_config("smollm-360m"))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    outs = []
    for _ in range(2):
        eng = ServeEngine(api, params, max_slots=2, max_seq=64)
        eng.submit(np.array([5, 6, 7]), max_new_tokens=6)
        done = eng.run_until_done()
        outs.append(done[0].output)
    assert outs[0] == outs[1]


def test_serve_engine_matches_manual_decode():
    """Engine output == hand-rolled prefill+decode loop (greedy)."""
    cfg = reduced(get_config("qwen3-1.7b")).replace(dtype="float32",
                                                    quantization="none")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(3))
    prompt = np.array([3, 1, 4, 1, 5])
    eng = ServeEngine(api, params, max_slots=2, max_seq=64)
    eng.submit(prompt, max_new_tokens=5)
    out_engine = eng.run_until_done()[0].output

    cache = api.init_cache(1, 64)
    lg, cache = api.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                            cache)
    toks = [int(jnp.argmax(lg[0, -1]))]
    pos = len(prompt)
    for _ in range(4):
        lg, cache = api.decode(params, jnp.array([toks[-1]]), cache,
                               jnp.int32(pos))
        toks.append(int(jnp.argmax(lg[0, -1])))
        pos += 1
    assert out_engine == toks


def test_prepare_params_quantizes_matrices():
    """Each projection becomes int8 codes in {-1, 0, 1} with one f32 gamma
    per stacked layer; norms and embeddings stay as they are, and a second
    pass changes nothing."""
    from repro.quant.bitnet import TernaryWeight

    cfg = reduced(get_config("smollm-360m"))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    qp = prepare_params(params)
    for group, names in (("attn", ("wq", "wk", "wv", "wo")),
                         ("mlp", ("w1", "w2", "w3"))):
        for name in names:
            tw = qp["blocks"][group][name]
            latent = params["blocks"][group][name]
            assert isinstance(tw, TernaryWeight), name
            assert tw.q.dtype == jnp.int8 and tw.q.shape == latent.shape
            assert set(np.unique(np.asarray(tw.q))) <= {-1, 0, 1}
            assert tw.gamma.dtype == jnp.float32
            assert tw.gamma.shape == (cfg.layers,)
    assert qp["blocks"]["ln1"] is params["blocks"]["ln1"]
    assert qp["embed"]["tokens"] is params["embed"]["tokens"]
    again = prepare_params(qp)
    assert again["blocks"]["attn"]["wq"] is qp["blocks"]["attn"]["wq"]


class _LogitRecorder(ServeEngine):
    """Keeps the logits of slot 0 at every sample."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.sampled = []

    def _sample(self, logits):
        self.sampled.append(np.asarray(logits[0], np.float32))
        return super()._sample(logits)


_PROMPT = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)


def _serve_one(api, params, new_tokens=8):
    eng = _LogitRecorder(api, params, max_slots=1, max_seq=64)
    req = eng.submit(_PROMPT, max_new_tokens=new_tokens)
    eng.run_until_done()
    return req.output, np.stack(eng.sampled)


def _bitnet_api(dtype="bfloat16"):
    cfg = reduced(get_config("bitnet-1.58b-kv")).replace(dtype=dtype)
    api = build_model(cfg)
    return api, api.init(jax.random.PRNGKey(0))


def test_serving_latent_equals_prepared():
    """Latent weights and ``prepare_params`` of them serve the same model:
    identical tokens and logits (prepared weights were once re-quantized in
    the step and served 0.69 times too small)."""
    api, params = _bitnet_api()
    toks_latent, lg_latent = _serve_one(api, params)
    toks_prep, lg_prep = _serve_one(api, prepare_params(params))
    assert toks_latent == toks_prep
    np.testing.assert_array_equal(lg_latent, lg_prep)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 8 * 2.0 ** -8)])
def test_served_codes_match_per_step_fake_quant(dtype, tol):
    """The engine's integer BitLinear over codes made once matches the old
    step, which fake-quantizes the latent weights every call (teacher-
    forced with the engine's tokens): within f32 summation order, or
    within bf16 rounding (unit roundoff 2**-8) carried through two layers
    and the head, relative to the largest logit."""
    api, params = _bitnet_api(dtype)
    toks, lg_engine = _serve_one(api, params)
    cache = api.init_cache(1, 64)
    lg, cache = api.prefill(params, {"tokens": jnp.asarray(_PROMPT[None])},
                            cache)
    rows = [lg[0, -1]]
    for i, tok in enumerate(toks[:-1]):
        lg, cache = api.decode(params, jnp.array([tok]), cache,
                               jnp.int32(len(_PROMPT) + i))
        rows.append(lg[0, -1])
    lg_old = np.asarray(jnp.stack(rows), np.float32)
    scale = np.abs(lg_old).max()
    np.testing.assert_allclose(lg_engine, lg_old, rtol=0, atol=tol * scale)
    if dtype == "float32":
        assert toks == [int(t) for t in np.argmax(lg_old, axis=-1)]


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, through every nested jaxpr (the jit,
    the scan over layers)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in eqn.params.values():
            for j in (sub if isinstance(sub, (list, tuple)) else [sub]):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _shape(v):
    return tuple(getattr(v.aval, "shape", ()))


def _weight_quant_ops(jaxpr, shapes):
    """abs / reductions / rounding whose operand has a weight's shape."""
    return [eqn.primitive.name for eqn in _eqns(jaxpr)
            if eqn.primitive.name in ("abs", "round")
            or eqn.primitive.name.startswith("reduce")
            for v in eqn.invars if _shape(v) in shapes]


def test_decode_does_not_quantize_weights():
    """The engine's decode program reads codes: no abs, reduce or round over
    a weight-shaped operand (the latent per-step path has them)."""
    from repro.quant.bitnet import TernaryWeight

    api, params = _bitnet_api()
    eng = ServeEngine(api, params, max_slots=2, max_seq=64)
    is_tw = lambda x: isinstance(x, TernaryWeight)
    served = [x for x in jax.tree.leaves(eng.params, is_leaf=is_tw)
              if is_tw(x)]
    shapes = {s for tw in served for s in (tw.q.shape, tw.q.shape[1:])}
    tok = jnp.zeros((2,), jnp.int32)
    args = (tok, eng.cache, jnp.zeros((2,), jnp.int32))
    prog = jax.make_jaxpr(eng._decode)(eng.params, *args)
    assert _weight_quant_ops(prog.jaxpr, shapes) == []
    old = jax.make_jaxpr(api.decode)(params, *args)
    assert "abs" in _weight_quant_ops(old.jaxpr, shapes)


@pytest.mark.parametrize("arch", ["bitnet-1.58b-kv", "moonlight-16b-a3b"])
def test_decode_writes_rows_into_the_carried_cache(arch):
    """The decode's layer scan carries the stacked cache and writes each
    step's rows into it: the only writes on a cache-shaped operand are
    scatters of unique one-row windows into the whole stack, none into one
    layer's lanes (the per-slot write a vmapped ``dynamic_update_slice``
    made, which left the compiler slicing out and restacking every lane),
    and the cache is no scan's per-layer input or output."""
    cfg = reduced(get_config(arch))
    api = build_model(cfg)
    eng = ServeEngine(api, api.init(jax.random.PRNGKey(0)), max_slots=2,
                      max_seq=64)
    stacks = {tuple(leaf.shape) for leaf in jax.tree.leaves(eng.cache)}
    lanes = {s[1:] for s in stacks}
    slots = jnp.zeros((2,), jnp.int32)
    prog = jax.make_jaxpr(eng._decode)(eng.params, slots, eng.cache, slots)
    writes = [e for e in _eqns(prog.jaxpr)
              if e.primitive.name in ("scatter", "dynamic_update_slice")
              and _shape(e.invars[0]) in stacks | lanes]
    assert len(writes) == 2 * (1 + bool(cfg.first_k_dense))
    for e in writes:
        assert e.primitive.name == "scatter"
        assert _shape(e.invars[0]) in stacks
        assert e.params["unique_indices"]
        assert len(e.params["dimension_numbers"].update_window_dims) == 1
    scans = [e for e in _eqns(prog.jaxpr) if e.primitive.name == "scan"]
    assert scans
    for e in scans:
        first_x = e.params["num_consts"] + e.params["num_carry"]
        assert not {_shape(v) for v in e.invars[first_x:]} & stacks
        ys = e.outvars[e.params["num_carry"]:]
        assert not {_shape(v) for v in ys} & stacks


@pytest.mark.parametrize("arch,quantization,leaves", [
    ("bitnet-1.58b-kv", "bitnet", 7),
    ("bitnet-1.58b-kv", "none", 0),
    ("granite-moe-1b-a400m", "bitnet", 7),
    ("mamba2-130m", "bitnet", 4),
    ("zamba2-7b", "bitnet", 11),
])
def test_ternary_projection_gauge(arch, quantization, leaves):
    """The engine reports the projection leaves it serves as codes (the
    attention and MLP projections, an SSM's in/out projections, a hybrid's
    shared block besides); a model that is not quantized is served with
    the very params it was given."""
    from repro.obs.metrics import MetricsRegistry

    cfg = reduced(get_config(arch)).replace(quantization=quantization)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    reg = MetricsRegistry()
    eng = ServeEngine(api, params, max_slots=2, max_seq=32, metrics=reg)
    assert eng.ternary_projection_leaves == leaves
    assert reg.get("serve_ternary_projection_leaves").value() == leaves
    if not leaves:
        assert eng.params is params
    req = eng.submit(np.arange(1, 6), max_new_tokens=3)
    eng.run_until_done()
    assert req.done and len(req.output) == 3
    assert all(0 <= t < cfg.vocab for t in req.output)


def test_prompt_boundary_completions():
    """EOS sampled at prefill and a 1-token budget both complete the
    request AT admission — one output token, no decode slot occupied."""
    cfg = reduced(get_config("smollm-360m"))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    # discover the greedy prefill-sampled token for this prompt
    probe = ServeEngine(api, params, max_slots=2, max_seq=64)
    probe.submit(np.array([5, 6, 7]), max_new_tokens=4)
    first = probe.run_until_done()[0].output[0]

    eng = ServeEngine(api, params, max_slots=2, max_seq=64)
    r_eos = eng.submit(np.array([5, 6, 7]), max_new_tokens=4, eos_id=first)
    r_one = eng.submit(np.array([5, 6, 7]), max_new_tokens=1)
    eng.step()
    assert r_eos.done and r_eos.output == [first]
    assert r_one.done and len(r_one.output) == 1     # not 2
    assert not r_eos.truncated and not r_one.truncated
    assert eng._active() == []                       # no slot ever taken
    assert len(eng.finished) == 2


def test_submit_validates_prompt_and_budget():
    """Overlong prompts would silently clamp the cache write and decode a
    corrupted lane — submit must reject them (and degenerate inputs)."""
    cfg = reduced(get_config("smollm-360m"))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    eng = ServeEngine(api, params, max_slots=2, max_seq=16)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(np.arange(1, 18))                 # 17 tokens > 16
    with pytest.raises(ValueError, match="non-empty"):
        eng.submit(np.array([], np.int32))
    with pytest.raises(ValueError, match="non-empty"):
        eng.submit(np.array([[1, 2]]))               # 2-D
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.array([1, 2]), max_new_tokens=0)
    assert eng.queue == []
    # an exactly-window-sized prompt is legal (completes at its boundary)
    edge = eng.submit(np.arange(1, 17), max_new_tokens=4)
    eng.run_until_done()
    assert edge.done and len(edge.output) == 1


def test_window_truncation_flagged():
    """Requests cut off by the cache window carry ``Request.truncated``;
    natural (budget/EOS) completions do not."""
    cfg = reduced(get_config("smollm-360m"))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    eng = ServeEngine(api, params, max_slots=1, max_seq=8)
    cut = eng.submit(np.arange(1, 5), max_new_tokens=32)   # 4 + 32 >> 8
    nat = eng.submit(np.arange(1, 4), max_new_tokens=2)
    eng.run_until_done()
    assert cut.done and cut.truncated
    assert len(cut.output) < cut.max_new_tokens
    assert nat.done and not nat.truncated and len(nat.output) == 2
    # prompt filling the whole window: truncated at the prefill boundary
    window = ServeEngine(api, params, max_slots=1, max_seq=8)
    edge = window.submit(np.arange(1, 9), max_new_tokens=4)
    window.run_until_done()
    assert edge.done and edge.truncated and len(edge.output) == 1
    # ... unless one token was all it wanted anyway
    happy = ServeEngine(api, params, max_slots=1, max_seq=8)
    one = happy.submit(np.arange(1, 9), max_new_tokens=1)
    happy.run_until_done()
    assert one.done and not one.truncated


def test_prefill_chunk_bit_exact():
    """Chunked prefill == whole-prompt prefill: final logits, every cache
    leaf, and the decode continuation.

    Equal up to float32 summation order: XLA picks its matmul blocking per
    shape, so a 5/4/3-token chunk and a 12-token prompt add the same
    d_model=128 products in different orders.  That bounds each dot's
    difference by about 128 * 2**-24 ~ 1e-5 relative (measured: ~2e-6 on
    values of magnitude ~4); a wrong position, mask or cache offset moves
    values by O(1e-1) or more."""
    tol = dict(rtol=1e-5, atol=1e-5)
    cfg = reduced(get_config("qwen3-1.7b")).replace(dtype="float32",
                                                    quantization="none")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(1))
    prompt = np.arange(1, 13, dtype=np.int32)[None]   # 12 tokens

    whole_cache = api.init_cache(1, 32)
    lg_whole, whole_cache = api.prefill(
        params, {"tokens": jnp.asarray(prompt)}, whole_cache)

    cache = api.init_cache(1, 32)
    pos0 = 0
    for c in (5, 4, 3):                               # uneven chunks
        lg, cache = api.prefill_chunk(
            params, jnp.asarray(prompt[:, pos0:pos0 + c]), cache, pos0)
        pos0 += c
    np.testing.assert_allclose(lg[:, -1], lg_whole[:, -1], **tol)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(whole_cache)):
        np.testing.assert_allclose(a, b, **tol)
    # decode continues identically from either cache
    tok = jnp.argmax(lg_whole[:, -1], axis=-1).astype(jnp.int32)
    lg_a, _ = api.decode(params, tok, whole_cache, jnp.int32(12))
    lg_b, _ = api.decode(params, tok, cache, jnp.int32(12))
    np.testing.assert_allclose(lg_a, lg_b, **tol)


def test_inflight_engine_matches_legacy_bit_exact():
    """Tentpole acceptance: the in-flight engine (chunked prefill merged
    with decode) emits exactly the tokens the legacy engine does."""
    cfg = reduced(get_config("smollm-360m"))
    api = build_model(cfg)
    params = prepare_params(api.init(jax.random.PRNGKey(0)))
    prompts = [np.arange(1, 4 + 3 * i) for i in range(5)]   # 3..15 tokens
    outs = []
    for chunk in (None, 4):
        eng = ServeEngine(api, params, max_slots=3, max_seq=64,
                          prefill_chunk_tokens=chunk)
        reqs = [eng.submit(p, max_new_tokens=4 + i % 3)
                for i, p in enumerate(prompts)]
        done = eng.run_until_done()
        assert len(done) == 5
        outs.append([r.output for r in reqs])
    assert outs[0] == outs[1]


def test_inflight_requires_chunked_prefill_support():
    cfg = reduced(get_config("mamba2-130m"))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServeEngine(api, params, prefill_chunk_tokens=8)
    cfg2 = reduced(get_config("smollm-360m"))
    api2 = build_model(cfg2)
    with pytest.raises(ValueError, match=">= 1"):
        ServeEngine(api2, api2.init(jax.random.PRNGKey(0)),
                    prefill_chunk_tokens=0)


def test_kv_cache_plan():
    cfg = get_config("granite-20b")
    bpt = kv_bytes_per_token(cfg)
    assert bpt == 2 * 1 * 128 * 52 * 2
    budget = plan(cfg, batch=128, max_seq=32768,
                  hbm_bytes_per_chip=16e9, chips=256)
    assert budget.fits_hbm
    tight = plan(cfg, batch=128, max_seq=32768,
                 hbm_bytes_per_chip=16e9, chips=1)
    assert not tight.fits_hbm
