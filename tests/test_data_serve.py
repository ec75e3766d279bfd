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
    cfg = reduced(get_config("smollm-360m"))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    qp = prepare_params(params)
    w = np.asarray(qp["blocks"]["attn"]["wq"], np.float32)
    vals = np.unique(np.round(w / (np.abs(w)[w != 0].min() + 1e-12)))
    # ternary x scale: at most 3 distinct magnitudes per layer slice
    per_layer = np.asarray(qp["blocks"]["attn"]["wq"][0], np.float32)
    assert len(np.unique(per_layer)) <= 3


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
