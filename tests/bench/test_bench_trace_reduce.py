"""The reduction from a trace to device time, on a synthetic trace whose
answers are known, and the reading of a trace recorded here."""
import pytest

from bench import trace_reduce as tr

E = tr.Event


def synthetic():
    """Window 0..100; steps 10..40 and 50..90; a wait 40..50; device ops
    10..20, 15..30 (overlapping), 55..60, 58..62 and 95..110 (past the
    window's end)."""
    device = [E("fusion.1", 10, 20), E("fusion.2", 15, 30),
              E("bitlinear_matmul", 55, 60), E("bitlinear_matmul", 58, 62),
              E("copy", 95, 110)]
    spans = [E("bench.window", 0, 100), E("bench.step", 10, 40),
             E("bench.wait", 40, 50), E("bench.step", 50, 90)]
    return tr.Trace({"/device:TPU:0": device}, spans)


def test_op_name():
    assert tr.op_name("%while.14 = (s32[], bf16[16,1,2560]) while(%t)") \
        == "while.14"
    assert tr.op_name("%bitlinear_matmul.1 = s32[128,64] custom-call(%x)") \
        == "bitlinear_matmul.1"
    assert "bitlinear" not in tr.op_name(
        "%copy = s32[128,64] copy(s32[128,64] %bitlinear_matmul.1)")


def test_union_overlap_gaps():
    merged = tr.union([(15, 30), (10, 20), (55, 60), (58, 62), (95, 110)])
    assert merged == [(10, 30), (55, 62), (95, 110)]
    assert tr.overlap(merged, 0, 100) == 20 + 7 + 5
    assert tr.overlap(merged, 25, 57) == 5 + 2
    assert tr.gaps(merged, 0, 100) == [(0, 10), (30, 55), (62, 95)]


def test_busy_and_idle_share():
    r = tr.Reduced(synthetic(), chips=1)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(32e-9)
    assert 1 - r.busy_s / r.window_s == pytest.approx(0.68)


def test_device_time_per_span():
    r = tr.Reduced(synthetic(), chips=1)
    pairs = tr.paired(r, "bench.step", [{"k": 0}, {"k": 1}])
    assert [b for _, b in pairs] == pytest.approx([20e-9, 7e-9])
    assert r.op_seconds("bitlinear") == {
        "bitlinear_matmul": pytest.approx(9e-9)}


def test_idle_named_by_host_span():
    r = tr.Reduced(synthetic(), chips=1)
    idle = r.idle_by_span()
    # 0..10 outside any span; 30..55 has its midpoint in the wait;
    # 62..95 in the second step
    assert idle == {"host": pytest.approx(10e-9),
                    "bench.wait": pytest.approx(25e-9),
                    "bench.step": pytest.approx(33e-9)}
    b = r.breakdown()
    assert b["device_ops"][0][0] == "fusion.2"
    assert b["idle_gaps"][0][0] == "bench.step"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_chips_average():
    t = synthetic()
    t.devices["/device:TPU:1"] = []
    r = tr.Reduced(t, chips=2)
    assert r.busy_s == pytest.approx(16e-9)


def test_recorded_trace(tmp_path):
    """A trace recorded on this machine: the window and the step spans are
    read back from the ``.xplane.pb`` (no chip, so no device plane)."""
    import jax
    import jax.numpy as jnp

    from bench import harness

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    cell = harness.Cell("t", 1, {}, {}, [], [])
    ctx = harness.RunContext(cell, 0, 1.0, True, 0.0, None, {}, 1,
                             str(tmp_path))
    ctx.start_trace()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    ctx.stop_trace()
    t = tr.load(harness._trace_file(str(tmp_path)))
    r = tr.Reduced(t, chips=1)
    assert len(r.spans_named("bench.step")) == 3
    assert r.window_s > 0
    assert r.busy_s == 0.0
