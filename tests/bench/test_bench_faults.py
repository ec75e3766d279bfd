"""A whole run of each cell, cut to CPU size, past the harness's look for a
chip: sound it comes out correct; with the timed path broken underneath
(a token or an answer altered where it is produced) it comes out not
correct.  And on a machine without a TPU the command prints no result."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness
from tiny_cells import off_chip, tiny

SECONDS = 1.5


@pytest.fixture(autouse=True)
def _off_chip(monkeypatch):
    off_chip(monkeypatch)


def run(name, seed=7):
    return harness.run_cell(tiny(name), seed, SECONDS, False,
                            time.perf_counter())


@pytest.mark.parametrize("name", ["serve-gqa-decode", "serve-mha-longprompt",
                                  "legion-gqa-attn"])
def test_sound_run_is_correct(name):
    line = run(name)
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks"
    assert "setup_s" in line["metrics"]
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("name", ["serve-gqa-decode", "serve-mha-longprompt"])
def test_altered_token_is_not_correct(name, monkeypatch):
    from repro.serve.engine import ServeEngine

    sample = ServeEngine._sample

    def altered(self, logits):
        return (sample(self, logits) + 1) % logits.shape[-1]

    monkeypatch.setattr(ServeEngine, "_sample", altered)
    line = run(name)
    assert line["correct"] is False
    c = line["checks"]["logit_gap"]
    assert c["value"] > c["limit"]


def test_altered_answer_is_not_correct(monkeypatch):
    """The kernel's answer is altered from the window's open on (set-up's
    pass would stop the run before any window)."""
    import repro.kernels.bitlinear.ops as ops

    tile = ops.tile_gemm
    open_window = harness.RunContext.open_window
    broken = []

    def altered(*args, **kw):
        out = np.asarray(tile(*args, **kw)).copy()
        if broken:
            out[0, 0] += 1
        return out

    def opened(self):
        broken.append(True)
        return open_window(self)

    monkeypatch.setattr(ops, "tile_gemm", altered)
    monkeypatch.setattr(harness.RunContext, "open_window", opened)
    line = run("legion-gqa-attn")
    assert line["correct"] is False
    assert line["checks"]["stage_mismatch"]["value"] > 0


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "serve-gqa-decode", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    for ln in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(ln)
