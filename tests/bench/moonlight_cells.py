"""The Moonlight serve cell cut to a size the CPU runs in seconds, for the
tests: every width shrinks, the expert share stays a share (4 of 16
experts held, top-4), the engine's slots and the mix's lengths shrink,
and the limit with them (set from the CPU readings in
``test_bench_moonlight.py``; the cell's own limit comes from chip readings
at full size)."""
import copy

from bench import harness

CELL = "serve-moonlight-decode"
TINY_LIMIT = 1.65


def tiny(name: str = CELL) -> harness.Cell:
    cell = harness.find_cell(
        harness.load_json(harness.ROOT / "BENCHMARK.json"), name)
    cfg = copy.deepcopy(cell.config)
    mix = copy.deepcopy(cell.mix)
    cfg.update(num_hidden_layers=3, hidden_size=256, num_attention_heads=4,
               num_key_value_heads=4, kv_lora_rank=64, qk_nope_head_dim=32,
               qk_rope_head_dim=16, v_head_dim=32, intermediate_size=512,
               moe_intermediate_size=128, n_routed_experts=16,
               num_experts_per_tok=4, experts_held=4, expert_offset=4,
               vocab_size=1024, max_position_embeddings=256)
    cfg["check"] = {"logit_gap": TINY_LIMIT}
    cfg["engine"] = {"max_slots": 4, "max_seq": 256,
                     "prefill_chunk_tokens": 64}
    mix["prompt"].update(median=48, min=16, max=128, quantum=16)
    mix["output"].update(median=24, min=8, max=64)
    mix["requests"] = 24
    mix["trace"] = {"lead_s": 0.5, "span_s": 0.5}
    mix["check"]["requests"] = 3
    cell.config, cell.mix = cfg, mix
    return cell
