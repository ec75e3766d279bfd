"""The controls, at a size the CPU holds: the reference computed a step
below the configuration's precision, put in the program's place, has to
read far worse than the program on the same prompts and tokens (the
limits themselves come from chip readings at the cells' own sizes)."""
import time

import numpy as np
import pytest

from bench import harness
from bench.references import bitnet
from tiny_cells import tiny

import sys
sys.path.insert(0, str(harness.BENCH / "tools"))
import control  # noqa: E402


REQUESTS = 12


@pytest.mark.parametrize("name", ["serve-gqa-decode", "serve-mha-longprompt"])
def test_float8_control_reads_worse(name):
    """The float8 control reads 4-6 times the program here (at hidden 128
    bf16's own rounding is large enough to blur the two) and fails the
    tiny cells' limit.  The window finishes a short queue and every request
    is checked, so what is compared does not hang on the CPU's speed."""
    cell = tiny(name)
    cell.mix.update(arrival={"kind": "offline"}, requests=REQUESTS,
                    check={"requests": REQUESTS})
    r = control.serve_reading(cell, 2 ** 31 + 3, 8.0, harness.CompileClock(),
                              harness.peak_of("TPU v5 lite"))
    assert r["tokens"] > 200
    assert r["control"] > 3 * r["program"], r
    assert r["control"] > cell.config["check"]["logit_gap"] > r["program"]
    assert r["program_correct"] is True and r["control_correct"] is False


def test_int4_control_mismatches():
    r = control.legion_reading(tiny("legion-gqa-attn"), 5)
    assert r["program"] == 0
    assert r["control"] > 0
    assert r["program_correct"] is True and r["control_correct"] is False


def test_reference_weights_are_the_served_ones():
    """The reference makes each layer again from the seed; it equals, bit
    for bit, the layer the served run made in one call."""
    import jax.numpy as jnp

    from bench import serving

    cfg = tiny("serve-gqa-decode").config
    m = serving.model_sizes(cfg)
    served = bitnet.served_weights(m, 2 ** 33 + 1, jnp.bfloat16)
    for layer in (0, m["num_hidden_layers"] - 1):
        again = bitnet.layer_weights(m, 2 ** 33 + 1, layer, jnp.bfloat16)
        for k, v in again.items():
            want = np.asarray(served["layers"][k][layer], np.float32)
            assert np.array_equal(np.asarray(v), want), k
