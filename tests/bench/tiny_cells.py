"""Benchmark cells cut to a size the CPU runs in seconds, for the tests.

Each keeps its cell's driver, traffic shape and checks; the model's
sizes, the engine's slots and the mix's lengths shrink, and the serve
cells' limit with them.  At hidden 256 the program reads a logit gap of
0.04-0.10 and its float8 control 0.33-0.47 (CPU), so the limit there is
0.25; the cells' own limits come from chip readings at full size.
"""
import copy

from bench import harness


def off_chip(monkeypatch) -> None:
    """Let a whole run go past the harness's look for a TPU, with the peaks
    of the chip the cells are sized for."""
    monkeypatch.setattr(harness, "device_info", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})


def spec() -> dict:
    return harness.load_json(harness.ROOT / "BENCHMARK.json")


# cells whose files are kept for a later benchmark PR, out of BENCHMARK.json
# until their traffic is measured on the chip: (configuration, mix)
KEPT = {"serve-mha-longprompt": ("bitnet-1.58b", "longprompt_poisson")}


def find(name: str) -> harness.Cell:
    """A cell of ``BENCHMARK.json``, or one of ``KEPT`` with its files."""
    if name not in KEPT:
        return harness.find_cell(spec(), name)
    config, mix = KEPT[name]
    return harness.Cell(
        name=name, chips=1,
        config=harness.load_json(harness.BENCH / "configs" / f"{config}.json"),
        mix=harness.load_json(harness.BENCH / "traffic" / f"{mix}.json"),
        end_to_end=[m for m in spec()["end_to_end"] if m["name"] in (
            "setup_s", "itl_p95_ms", "output_tokens_per_s")], per_layer=[])


def tiny(name: str) -> harness.Cell:
    cell = find(name)
    cfg = copy.deepcopy(cell.config)
    mix = copy.deepcopy(cell.mix)
    if mix["driver"].startswith("serve"):
        mha = cfg["num_key_value_heads"] == cfg["num_attention_heads"]
        cfg.update(num_hidden_layers=2, hidden_size=256,
                   num_attention_heads=4, num_key_value_heads=4 if mha else 2,
                   head_dim=64,
                   intermediate_size=512, vocab_size=1024,
                   max_position_embeddings=256)
        cfg["check"] = {"logit_gap": 0.25}
        cfg["engine"] = {"max_slots": 4, "max_seq": 256,
                         "prefill_chunk_tokens": 64}
        mix["prompt"].update(median=48, min=16, max=128, quantum=16)
        mix["output"].update(median=24, min=8, max=64)
        mix["requests"] = 24
        mix["trace"] = {"lead_s": 0.5, "span_s": 0.5}
        mix["check"]["requests"] = 3
        if mix["arrival"]["kind"] == "poisson":
            mix["arrival"]["rate_per_s"] = 20.0
    else:
        cfg.update(hidden_size=256, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=128)
        cfg["legion"]["kernel_backend"] = "reference"
        mix["programs"] = 2
        mix["trace"] = {"lead_s": 0.0, "span_s": 1.0}
    cell.config, cell.mix = cfg, mix
    return cell
