"""Every configuration, traffic mix, driver and per-layer metric that
``BENCHMARK.json`` names is found by name in a file of its own, and the
file keeps to the benchmark's contract on names, units and keys."""
import re

import pytest

from bench import harness
from tiny_cells import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_keys_and_names():
    s = spec()
    assert set(s) == KEYS["top"]
    assert s["command"] == ["python3", "bench/run.py"]
    assert 1 <= s["run_seconds"] <= 51
    for kind, items in (("config", s["configs"]),
                        ("workload", s["workloads"]),
                        ("end_to_end", s["end_to_end"]),
                        ("per_layer", s["per_layer"])):
        names = [i["name"] for i in items]
        assert len(names) == len(set(names)), kind
        for i in items:
            assert set(i) - {"workloads"} == KEYS[kind], i
            assert NAME.match(i["name"]), i["name"]
            if "unit" in i:
                assert UNIT.match(i["unit"]) and i["better"] in (
                    "lower", "higher")
            for text in ("why", "layer", "source"):
                if text in i:
                    assert 1 <= len(i[text]) <= 200 and "\n" not in i[text]
    for e in s["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25


@pytest.mark.parametrize("cell", [w["name"] for w in spec()["workloads"]])
def test_cell_found_by_name(cell):
    s = spec()
    c = harness.find_cell(s, cell)
    assert (harness.BENCH / "drivers" / f"{c.mix['driver']}.py").is_file()
    for m in c.per_layer:
        assert harness.metric_file(m["name"]).is_file()
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer


def test_suffixed_metrics_share_their_stem_reader():
    """``device_idle_share.serve`` and ``.legion`` have no file of their
    own and read through ``device_idle_share.py``; a metric's own file
    wins over its stem's."""
    stem = harness.BENCH / "metrics" / "device_idle_share.py"
    assert harness.metric_file("device_idle_share.serve") == stem
    assert harness.metric_file("device_idle_share.legion") == stem
    own = harness.BENCH / "metrics" / "decode_step_ms.py"
    assert harness.metric_file("decode_step_ms") == own


def test_configs_in_own_files():
    s = spec()
    files = [c["file"] for c in s["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in s["workloads"]}
    for c in s["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/configs/")
        data = harness.load_json(harness.ROOT / c["file"])
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]


def test_metrics_point_at_their_cells():
    s = spec()
    e2e = {m["name"]: m for m in s["end_to_end"]}
    cells = {w["name"] for w in s["workloads"]}
    layers = {m["layer"] for m in s["per_layer"]}
    for m in s["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        target = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(target.get("workloads", cells))
        if m["unit"] == "%" and ("roofline" in m["name"]
                                 or "mfu" in m["name"]):
            assert m["better"] == "higher"
    assert layers <= {"engine", "model step", "kernels", "device",
                      "legion executor"}


def test_paths_hold_the_benchmark():
    s = spec()
    assert s["paths"] == ["bench", "tests/bench"]
    for w in s["workloads"]:
        assert (harness.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4)
