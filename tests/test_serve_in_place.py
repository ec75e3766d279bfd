"""The engine's cache is one buffer, updated in place.

``ServeEngine`` donates its cache to the decode, the prefill chunk and the
slot write, and the layer scan writes each step's K/V rows into the carried
stack.  These tests hold every such call bit for bit to the same program
run without donation on copies of its inputs, and check that the mechanism
engages: the cache passed in is consumed, the counter counts each call, and
the compiled programs alias the cache to their result.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import build_model
from repro.models.attention import layer_lanes, write_rows
from repro.obs.metrics import MetricsRegistry
from repro.serve import ServeEngine
from repro.serve import engine as engine_mod
from repro.serve.kv_cache import kv_bytes_per_token
from repro.serve.paged_kv import PagedKVCache

SLOTS, MAX_SEQ = 3, 48


def _engine(arch, chunk, **kw):
    cfg = reduced(get_config(arch))
    api = build_model(cfg)
    return ServeEngine(api, api.init(jax.random.PRNGKey(1)), max_slots=SLOTS,
                       max_seq=MAX_SEQ, prefill_chunk_tokens=chunk, **kw)


def _consumed(tree) -> bool:
    return all(leaf.is_deleted() for leaf in jax.tree.leaves(tree))


def _assert_identical(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _checked(run, plain, calls, name):
    """``run`` (a donating program) after ``plain`` (the same function under
    a plain jit) on copies of the inputs: equal results, input consumed."""
    def call(*args):
        donated = args[0] if name == "slot_write" else args[2]
        want = plain(*jax.tree.map(jnp.copy, args))
        got = run(*args)
        assert _consumed(donated), name
        _assert_identical(got, want)
        calls[name] += 1
        return got
    return call


@pytest.mark.parametrize("arch,chunk,pages", [
    ("bitnet-1.58b-kv", 8, None),     # GQA, in-flight
    ("bitnet-1.58b-kv", None, None),  # GQA, legacy
    ("bitnet-1.58b-kv", 8, 14),       # GQA, in-flight, paged, preempting
    ("bitnet-1.58b", 8, None),        # MHA
    ("moonlight-16b-a3b", 8, None),   # MLA, two stacks, held experts
    ("zamba2-7b", None, None),        # hybrid: SSM state and shared attn
])
def test_in_place_engine_matches_plain_programs(monkeypatch, arch, chunk,
                                                pages):
    """Every decode, chunk and slot write the engine makes returns the same
    logits, tokens and cache, bit for bit, as the plain ``api.decode`` /
    ``api.prefill_chunk`` / slot write under an undonated jit on copies."""
    paged = (PagedKVCache(total_pages=pages, page_tokens=4) if pages
             else None)
    eng = _engine(arch, chunk, paged_kv=paged)
    api = eng.api
    calls = {"decode": 0, "chunk": 0, "slot_write": 0}
    eng._decode = _checked(eng._decode, jax.jit(api.decode), calls,
                           "decode")
    if chunk:
        eng._prefill_chunk = _checked(eng._prefill_chunk,
                                      jax.jit(api.prefill_chunk), calls,
                                      "chunk")
    write = engine_mod._write_slot
    monkeypatch.setattr(engine_mod, "_write_slot", _checked(
        write, jax.jit(write.__wrapped__), calls, "slot_write"))
    rng = np.random.default_rng(5)
    reqs = [eng.submit(rng.integers(1, 500, n), max_new_tokens=m)
            for n, m in [(7, 12), (21, 9), (3, 20), (30, 6), (11, 15)]]
    eng.run_until_done()
    assert all(r.done for r in reqs)
    assert calls["decode"] >= 24
    assert calls["slot_write"] >= len(reqs)
    assert (calls["chunk"] > 0) == bool(chunk)
    if pages:
        assert eng.preemptions > 0


@pytest.mark.parametrize("chunk", [8, None])
def test_engine_counts_each_call_that_consumed_its_cache(chunk):
    """After a chunked or whole prefill, its slot write and two decodes,
    every cache passed in is deleted, ``serve_cache_inplace_calls`` equals
    the calls, and ``serve_cache_inplace_bytes`` holds the batch cache's
    bytes (the KV bytes of every slot's lane)."""
    metrics = MetricsRegistry()
    eng = _engine("bitnet-1.58b-kv", chunk, metrics=metrics)
    passed = []

    def spy(name, run):
        def call(params, x, cache, pos):
            passed.append((name, cache))
            return run(params, x, cache, pos)
        return call

    eng._decode = spy("decode", eng._decode)
    if chunk:
        eng._prefill_chunk = spy("chunk", eng._prefill_chunk)
    land = eng._land

    def spy_land(i, lane, uid, tokens):
        passed.append(("slot_write", eng.cache))
        land(i, lane, uid, tokens)

    eng._land = spy_land
    eng.submit(np.arange(1, 12), max_new_tokens=3)     # 11 tokens: 8 + 3
    eng.run_until_done()
    kinds = [name for name, _ in passed]
    assert kinds.count("chunk") == (2 if chunk else 0)
    assert kinds.count("slot_write") == 1
    assert kinds.count("decode") == 2
    assert all(_consumed(cache) for _, cache in passed)
    snap = metrics.snapshot()
    assert snap["serve_cache_inplace_calls"]["series"][""] == len(passed)
    nbytes = kv_bytes_per_token(eng.cfg) * SLOTS * MAX_SEQ
    assert sum(leaf.nbytes for leaf in jax.tree.leaves(eng.cache)) == nbytes
    assert snap["serve_cache_inplace_bytes"]["series"][""] == nbytes


def _aliased_parameters(compiled_text: str) -> int:
    header = compiled_text.split("\n", 1)[0]
    return len(re.findall(r"\{[\d,]*\}: \(\d+, \{[\d,]*\}, may-alias\)",
                          header))


@pytest.mark.parametrize("arch,leaves", [("bitnet-1.58b-kv", 2),
                                         ("moonlight-16b-a3b", 4)])
@pytest.mark.parametrize("program", ["decode", "chunk", "slot_write"])
def test_programs_alias_the_cache_to_their_result(arch, leaves, program):
    """The compiled decode, chunk and slot write each alias every leaf of
    the cache they are given to their result (``input_output_alias``): K
    and V, or for the two-stack latent cache both leaves of each stack."""
    eng = _engine(arch, 8)
    lane = eng.api.init_cache(1, MAX_SEQ)
    slots = jnp.zeros((SLOTS,), jnp.int32)
    lowered = {
        "decode": lambda: eng._decode.lower(eng.params, slots, eng.cache,
                                            slots),
        "chunk": lambda: eng._prefill_chunk.lower(
            eng.params, jnp.zeros((1, 8), jnp.int32), lane, 0),
        "slot_write": lambda: engine_mod._write_slot.lower(eng.cache, lane,
                                                           1),
    }[program]()
    text = lowered.compile().as_text()
    assert "input_output_alias" in text
    assert _aliased_parameters(text) == leaves


@pytest.mark.parametrize("layer", [None, 1])
@pytest.mark.parametrize("pos", [5, [0, 7, 31]])
def test_write_rows_writes_only_the_rows(layer, pos):
    """``write_rows`` puts each slot's rows at its positions (of one layer
    of a stacked leaf) and leaves every other element as it was; a
    position past the lane's end drops its row."""
    rng = np.random.default_rng(0)
    lead = () if layer is None else (2,)
    leaf = rng.normal(size=lead + (3, 2, 32, 4)).astype(np.float32)
    s = 2 if np.ndim(pos) == 0 else 1
    rows = rng.normal(size=(3, s, 2, 4)).astype(np.float32)
    at = jnp.asarray(pos, jnp.int32)
    got = np.asarray(write_rows(jnp.asarray(leaf), jnp.asarray(rows), at,
                                layer))
    want = leaf.copy()
    view = want if layer is None else want[layer]
    for b in range(3):
        p = pos if np.ndim(pos) == 0 else pos[b]
        view[b, :, p:p + s] = rows[b].transpose(1, 0, 2)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(layer_lanes(jnp.asarray(got), layer)), view)
    if np.ndim(pos):
        past = write_rows(jnp.asarray(leaf), jnp.asarray(rows),
                          jnp.asarray([32, 40, 99], jnp.int32), layer)
        np.testing.assert_array_equal(np.asarray(past), leaf)
