"""The work counts of ``bench/work`` against hand arithmetic, for both
configurations, and the rules that keep every share of a roofline or a
peak at or under 100% for any implementation of the same work."""
import math

import pytest

from bench import harness
from bench.work import legion as lwork
from bench.work import serve as work
LOG2_3_BYTES = math.log2(3) / 8


def config(name):
    return harness.load_json(harness.BENCH / "configs" / f"{name}.json")


# (projection params per layer, KV bytes per token, all parameters)
HAND = {
    # q,o 2560x2048 each; k,v 2560x2048 each; MLP 3 x 2560x6912
    "bitnet-1.58b": (2 * 5242880 + 2 * 5242880 + 53084160, 262144,
                     2451868160),
    # k,v 2560x512 each
    "bitnet-1.58b-kv": (2 * 5242880 + 2 * 1310720 + 53084160, 65536,
                        2200209920),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_counts_by_hand(name):
    m = config(name)
    proj, kv, total = HAND[name]
    assert work.projection_params(m) == proj
    assert work.kv_bytes_per_token(m) == kv
    norms = (2 * m["num_hidden_layers"] + 1) * m["hidden_size"]
    embed = m["vocab_size"] * m["hidden_size"]
    assert proj * m["num_hidden_layers"] + embed + norms == total
    assert m["memory"]["parameters_bytes"] == 2 * total
    assert m["memory"]["kv_bytes_per_token"] == kv
    assert m["memory"]["kv_lane_bytes"] == kv * m["engine"]["max_seq"]
    assert (m["memory"]["kv_cache_bytes"]
            == kv * m["engine"]["max_seq"] * m["engine"]["max_slots"])


def test_gqa_decode_step_by_hand():
    """16 slots at position 449: ternary weights + bf16 head and norms +
    450 positions of KV each."""
    m = config("bitnet-1.58b-kv")
    ops, nbytes = work.decode_step(m, [449] * 16)
    proj = HAND["bitnet-1.58b-kv"][0] * 32
    head = 32000 * 2560
    weights = proj * LOG2_3_BYTES + 2 * head + 2 * 65 * 2560
    assert nbytes == pytest.approx(
        weights + 16 * 450 * 65536 + 16 * 2560 * 2)
    assert weights == pytest.approx(0.5835e9, rel=1e-3)
    attn = 4 * 16 * 128 * 450 * 32
    assert ops == pytest.approx(16 * (2 * proj + 2 * head + attn))


def test_chunk_by_hand():
    m = config("bitnet-1.58b")
    ops, nbytes = work.prefill_chunk(m, 256, 512, last=False)
    proj = HAND["bitnet-1.58b"][0] * 32
    attn = sum(4 * 16 * 128 * (512 + i + 1) * 32 for i in range(256))
    assert ops == pytest.approx(256 * 2 * proj + attn)
    assert ops == pytest.approx(1.24e12, rel=0.02)
    last_ops, _ = work.prefill_chunk(m, 256, 512, last=True)
    assert last_ops - ops == 2 * 32000 * 2560
    assert nbytes == pytest.approx(work.weight_bytes(m) + 768 * 262144
                                   + 256 * 2560 * 2)


def test_least_counts_are_least():
    """A ternary weight is read at log2(3) bits, a KV position once, and
    the least time takes the faster of the int8 and bf16 peaks."""
    assert work.TERNARY_BYTES == LOG2_3_BYTES
    m = config("bitnet-1.58b-kv")
    one = work.decode_step(m, [0])[1] - work.decode_step(m, [])[1]
    assert one == work.kv_bytes_per_token(m) + m["hidden_size"] * 2
    peak = harness.peak_of("TPU v5 lite")
    assert peak["int8_ops_per_s"] >= peak["bf16_flops_per_s"]
    assert work.least_seconds(393e12, 0, peak) == pytest.approx(1.0)
    assert work.least_seconds(0, 819e9, peak) == pytest.approx(1.0)


def test_legion_counts_by_hand():
    ops, nbytes = lwork.tile_call(128, 128, 64, 2)
    assert ops == 2 * 128 * 128 * 64
    assert nbytes == 128 * 128 + 128 * 64 * 2 / 8 + 4 * 128 * 64
    from repro.core.workloads import AttentionSpec, attention_workloads
    spec_ = AttentionSpec(name="kv", layers=1, hidden=2560, heads=16,
                          kv_heads=4, head_dim=128, seq_len=128)
    total = lwork.program_ops(attention_workloads(spec_))
    qkv = 2 * 128 * 2560 * 128 * 24
    attn = 2 * (2 * 128 * 128 * 128 * 16)
    out = 2 * 128 * 2048 * 2560
    assert total == qkv + attn + out
    assert total == pytest.approx(3.49e9, rel=0.01)
