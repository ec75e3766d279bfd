"""The Moonlight serve cell and the four-chip Legion cell, cut to CPU size:
whole runs past the harness's look for a chip, the float8 control, the
reference's weights, the work counts and the new readers on known values.

At the tiny size (hidden 256, 4 of 16 experts held, top-4, bf16) the
widest gap over every request of a seed's mix reads 0.53-1.42 for the
program and 1.82-2.63 for its float8 control (CPU, seeds 1-7, 2**31 + 3
and 2**31 + 12345; the first 12 requests of 2**31 + 12345 read 1.47), so
the tiny limit is 1.65; the cell's own limit comes from chip readings at
full size.  Which requests a short window checks depends on its pace, so
the limit holds for every request, not for a sample.  The gap is the
widest over every served token, and a top-k choice that turns on a gap
smaller than the rounding moves a token's logits by far more than the
rounding: both sides read higher than a dense model's (the request that
reads 1.35 in bf16 reads 0.007 with the program in float32).
"""
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, program_trace
from bench import trace_reduce as tr
from bench.references import moonlight as ref
from bench.work import moe as work
from moonlight_cells import tiny
from tiny_cells import off_chip

sys.path.insert(0, str(harness.BENCH / "tools"))
import control_moe  # noqa: E402

PEAK = harness.peak_of("TPU v5 lite")
E = tr.Event


def run(seed=7, trace=False):
    return harness.run_cell(tiny(), seed, 1.5, trace, time.perf_counter())


def test_sound_run_is_correct(monkeypatch):
    off_chip(monkeypatch)
    line = run()
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks"
    m = line["metrics"]
    assert {"setup_s", "output_tokens_per_s", "itl_p95_ms"} <= set(m)
    assert line["attempted"] > 0 and line["failed"] == 0


def test_window_runs_on_a_frozen_start_up_heap(monkeypatch, capsys):
    """The set-up heap is frozen for the window alone: thawed after it, so
    freeing the engine before the reference collects its state; the
    collector's pauses in the window are in the notes."""
    import gc

    from bench import serving

    off_chip(monkeypatch)
    frozen = []
    run_window = serving.run_window

    def watched(*a, **kw):
        frozen.append(gc.get_freeze_count())
        return run_window(*a, **kw)

    monkeypatch.setattr(serving, "run_window", watched)
    before = gc.get_freeze_count()
    line = run()
    assert line["correct"] is True
    assert frozen and frozen[0] > before + 10_000
    assert gc.get_freeze_count() <= before
    notes = json.loads(next(ln for ln in capsys.readouterr().out.splitlines()
                            if ln.startswith("notes "))[len("notes "):])
    n, seconds, longest = notes["gc_in_window"]
    assert n >= 0 and 0.0 <= longest <= seconds
    assert notes["gc_full_s"] > 0.0 and notes["gc_tracked"] > 0


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serve.engine import ServeEngine

    off_chip(monkeypatch)
    sample = ServeEngine._sample

    def altered(self, logits):
        return (sample(self, logits) + 1) % logits.shape[-1]

    monkeypatch.setattr(ServeEngine, "_sample", altered)
    line = run()
    assert line["correct"] is False
    c = line["checks"]["logit_gap"]
    assert c["value"] > c["limit"]


def test_traced_run_reads_the_routing(monkeypatch):
    """A traced tiny run: the engine's routing counts reach the readers
    (the CPU has no device plane, so the device readers read idle or
    nothing)."""
    off_chip(monkeypatch)
    line = run(11, trace=True)
    assert line["correct"] is True
    m = line["metrics"]
    assert m["moe_tokens_per_expert"]["value"] >= 1.0
    assert 1.0 <= m["decode_batch_mean.moonlight"]["value"] <= 4.0
    assert "decode_program_ms.moonlight" not in m


def test_float8_control_reads_worse():
    """The float8 control, in the program's place on the same prompts and
    served tokens, fails the tiny limit that the program passes."""
    cell = tiny()
    cell.mix.update(requests=12, check={"requests": 12})
    r = control_moe.reading(cell, 2 ** 31 + 3, 8.0, harness.CompileClock(),
                            PEAK)
    assert r["tokens"] > 200
    assert r["control"] > 1.5 * r["program"], r
    assert r["program_correct"] is True and r["control_correct"] is False


def test_reference_weights_are_the_served_ones():
    """The reference makes each layer again from the seed; it equals, bit
    for bit, the layer the served run made in one call (a dense layer and
    an expert layer)."""
    m = ref.sizes(tiny().config)
    served = ref.served_weights(m, 2 ** 33 + 1, jnp.bfloat16)
    for layer, group, i in ((0, "dense_layers", 0), (2, "layers", 1)):
        again = ref.layer_weights(m, 2 ** 33 + 1, layer, jnp.bfloat16)
        assert set(again) == set(served[group])
        for k, v in again.items():
            want = np.asarray(served[group][k][i], np.float32)
            assert np.array_equal(np.asarray(v), want), k


M = {"num_hidden_layers": 3, "hidden_size": 8, "num_attention_heads": 2,
     "kv_lora_rank": 4, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
     "v_head_dim": 2, "intermediate_size": 16, "moe_intermediate_size": 4,
     "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 2,
     "first_k_dense_replace": 1, "vocab_size": 32}


def _attn(c):
    """Attention of one token over ``c`` positions, 3 layers: absorbed
    (4*2*2*4 + 2*2*(2*4 + 2)*c) or expanded (2*4*2*4*c + 2*2*6*c)."""
    return 3 * min(64 + 40 * c, 88 * c)


def test_work_counts_by_hand():
    """A decode step of two slots at positions 3 and 5 that reached 3 held
    (layer, expert) pairs with 4 routed tokens; a last chunk of 4 tokens
    at position 2."""
    t = work.TERNARY_BYTES
    attn = 8 * 2 * 4 + 8 * 6 + 4 * 2 * 4 + 2 * 2 * 8       # 64+48+32+32
    assert work.attn_params(M) == attn == 176
    expert = 3 * 8 * 4
    token = 3 * attn + 3 * 8 * 16 + 2 * 2 * expert          # 528+384+384
    assert work.token_params(M) == token == 1296
    per_token = 2 * token + 2 * 8 * 8 * 2                   # + 2 routers
    head = 2 * 32 * 8
    assert _attn(1) == 3 * 88 and _attn(6) == 3 * 304       # expanded less
    ops, nbytes = work.decode_step(M, [3, 5], routed=4, experts_hit=3)
    assert ops == 2 * (per_token + head) + _attn(4) + _attn(6) \
        + 2 * 4 * expert
    fixed = ((token + 3 * expert) * t + 32 * 8 * 2 + 2 * 9 * 8 * 4
             + (3 * (16 + 4) + 8) * 2)
    cache = (4 + 2) * 2 * 3 * (4 + 6)
    assert nbytes == pytest.approx(fixed + cache + 2 * 8 * 2)
    ops_c, _ = work.prefill_chunk(M, 4, 2, last=True)
    assert ops_c == 4 * per_token + sum(map(_attn, (3, 4, 5, 6))) + head


def test_absorbed_attention_counts_least_at_length():
    """At the cell's widths the absorbed form is the lesser from two
    positions on, so decode's attention counts in it."""
    m = ref.sizes(harness.load_json(
        harness.BENCH / "configs" / "moonlight-16b-a3b-ep8.json"))
    h, r = m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    for c in (2, 128, 2048):
        absorbed = 4 * h * nope * r + 2 * h * (2 * r + rope) * c
        assert work._attn_ops(m, c) == absorbed * m["num_hidden_layers"]


def _view(steps, reduced):
    m = ref.sizes(harness.load_json(
        harness.BENCH / "configs" / "moonlight-16b-a3b-ep8.json"))
    return harness.View({"steps": steps, "traced_steps": steps, "model": m},
                        reduced, PEAK)


def _read(name, view):
    return harness.load_module(harness.metric_file(name)).read(view)


def _trace():
    """Window 0..100; two engine steps 10..40 and 50..90, the device busy
    20..30 and 60..80."""
    spans = [E("bench.window", 0, 100), E("bench.step", 10, 40),
             E("bench.step", 50, 90)]
    device = [E("fusion", 20, 30), E("fusion", 60, 80)]
    return program_trace.Reduced(program_trace.Trace(
        {"/device:TPU:0": device}, spans, {"/device:TPU:0": []}), 1)


def test_new_readers_on_known_values():
    steps = [{"positions": [5, 9], "chunks": [], "moe_routed": 12,
              "moe_experts_hit": 8},
             {"positions": [6], "chunks": [(64, 0, False)],
              "moe_routed": 6, "moe_experts_hit": 4}]
    view = _view(steps, _trace())
    assert _read("moe_tokens_per_expert", view) == pytest.approx(18 / 12)
    least = work.least_seconds(*work.decode_step(
        view.observed["model"], [5, 9], 12, 8), PEAK)
    # the decode-only step alone, 10 ns busy
    assert _read("moe_decode_roofline", view) == pytest.approx(
        100 * least / 10e-9)


def test_new_readers_read_nothing_without_routing_counts():
    """Where the program keeps no routing counts (a program before them),
    the two readers return nothing and do not raise."""
    steps = [{"positions": [5, 9], "chunks": []}]
    view = _view(steps, _trace())
    assert _read("moe_tokens_per_expert", view) is None
    assert _read("moe_decode_roofline", view) is None


_FOUR_CHIPS = """
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
from bench import harness
import tiny_cells
harness.device_info = lambda chips: {{"platform": "cpu",
                                      "kind": "TPU v5 lite", "count": 4}}
cell = tiny_cells.tiny("legion-gqa-attn-4chip")
for trace in (False, True):
    line = harness.run_cell(cell, 2 ** 31 + 5, 1.0, trace,
                            time.perf_counter())
    print(json.dumps(line, default=float))
"""


def test_four_chip_cell_on_four_host_devices():
    """The four-chip Legion cell on four virtual CPU devices (its own
    process): every pass bit-exact against the reference, the shards on
    four devices, and the traced run reads the executor's spans."""
    root = str(harness.ROOT)
    code = _FOUR_CHIPS.format(root=root, src=os.path.join(root, "src"),
                              tests=os.path.join(root, "tests", "bench"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    notes = [json.loads(ln[len("notes "):]) for ln in p.stdout.splitlines()
             if ln.startswith("notes ")]
    assert len(lines) == 2
    plain, traced = lines
    for line in lines:
        assert line["correct"] is True, line["checks"]
        assert line["checks"]["stage_mismatch"]["value"] == 0
    assert plain["metrics"]["legion_pass_s"]["value"] > 0
    assert all(n["devices_used"] == [4] for n in notes)
    m = traced["metrics"]
    assert 0 < m["legion_check_share.sharded"]["value"] < 100
    assert 0 < m["legion_execute_share.sharded"]["value"] < 100
