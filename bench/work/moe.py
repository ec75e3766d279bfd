"""Operations and bytes that serving one chip's share of an expert-parallel
MoE model with latent attention needs (Moonlight-16B-A3B), from shapes and
the engine's routing counts.

The counts are fixed by the work, not by any implementation, so that no
implementation can read over its roofline:

* a projection weight counts at log2(3) bits, the least that any ternary
  packing reads: attention (``q``, ``kv_a``, ``kv_b``, ``o``), the leading
  dense layers' MLP, the shared experts, and of the routed experts only
  the held ones that the step's counter says tokens reached
  (``moe_experts_hit`` pairs of layer and expert);
* the untied head counts at bf16, whole; the embedding rows read, the
  norm gains at bf16; the routers at float32, as the block computes them;
* the latent cache counts 1,152 B a position and layer (a 512-wide latent
  and a 64-wide rotary key in bf16), each position a slot attends read
  once, the one it writes included;
* a multiply-add counts as 2 operations; the routed experts count for the
  tokens routed to held experts (``moe_routed``); attention counts, per
  token and layer, in the lesser of its two forms: absorbed, ``kv_b``
  folded into the query and the output (``4 * heads * nope * rank``) and,
  per position attended, scores over the latent and the rotary key and
  values over the latent (``2 * heads * (2 * rank + rope)``); or expanded,
  ``kv_b`` over every position attended (``2 * rank * heads * (nope +
  v)``) and scores and values per head (``2 * heads * (nope + rope +
  v)``);
* a prefill chunk needs logits only for a prompt's last position.
"""
from __future__ import annotations

from typing import Iterable, Tuple

# least_seconds is the serve cells' rule, read through this module too
from bench.work.serve import BF16, TERNARY_BYTES, least_seconds  # noqa: F401

F32 = 4


def _moe_layers(m: dict) -> int:
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def attn_params(m: dict) -> int:
    d, h, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    return d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v) \
        + h * v * d


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def token_params(m: dict) -> int:
    """Projection weights every token passes through, whatever its
    routing: attention, the dense layers' MLP, the shared experts."""
    d = m["hidden_size"]
    return (attn_params(m) * m["num_hidden_layers"]
            + m["first_k_dense_replace"] * 3 * d * m["intermediate_size"]
            + _moe_layers(m) * m["n_shared_experts"] * expert_params(m))


def cache_bytes_per_position(m: dict) -> int:
    """Latent and rotary key of one position over every layer, in bf16."""
    return ((m["kv_lora_rank"] + m["qk_rope_head_dim"]) * BF16
            * m["num_hidden_layers"])


def _fixed_bytes(m: dict, experts_hit: int) -> float:
    """Every weight a step reads once."""
    d, layers = m["hidden_size"], m["num_hidden_layers"]
    router = _moe_layers(m) * (d + 1) * m["n_routed_experts"] * F32
    norms = (layers * (2 * d + m["kv_lora_rank"]) + d) * BF16
    return ((token_params(m) + experts_hit * expert_params(m)) * TERNARY_BYTES
            + m["vocab_size"] * d * BF16 + router + norms)


def _token_ops(m: dict) -> int:
    """Per token, whatever its routing: projections and routers."""
    router = 2 * m["hidden_size"] * m["n_routed_experts"] * _moe_layers(m)
    return 2 * token_params(m) + router


def _attn_ops(m: dict, context: int) -> int:
    """Attention of one token over ``context`` positions in every layer,
    in the lesser of its two forms."""
    h, r, c = m["num_attention_heads"], m["kv_lora_rank"], context
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    absorbed = 4 * h * nope * r + 2 * h * (2 * r + rope) * c
    expanded = 2 * r * h * (nope + v) * c + 2 * h * (nope + rope + v) * c
    return min(absorbed, expanded) * m["num_hidden_layers"]


def _head_ops(m: dict) -> int:
    return 2 * m["vocab_size"] * m["hidden_size"]


def decode_step(m: dict, positions: Iterable[int], routed: int,
                experts_hit: int) -> Tuple[float, float]:
    """(operations, bytes) of one batched decode step; ``positions`` are
    the cache positions each active slot writes (it attends pos + 1);
    ``routed`` tokens reached ``experts_hit`` held (layer, expert) pairs."""
    positions = list(positions)
    ops = sum(_token_ops(m) + _head_ops(m) + _attn_ops(m, p + 1)
              for p in positions) + 2 * routed * expert_params(m)
    cache = cache_bytes_per_position(m) * sum(p + 1 for p in positions)
    rows = len(positions) * m["hidden_size"] * BF16
    return float(ops), float(_fixed_bytes(m, experts_hit) + cache + rows)


def prefill_chunk(m: dict, tokens: int, pos0: int, last: bool,
                  routed: int = 0, experts_hit: int = 0
                  ) -> Tuple[float, float]:
    """(operations, bytes) of one prompt chunk of ``tokens`` at ``pos0``;
    its routing counts, where known (the engine counts the decode's)."""
    ops = tokens * _token_ops(m) + 2 * routed * expert_params(m)
    ops += sum(_attn_ops(m, pos0 + i + 1) for i in range(tokens))
    if last:
        ops += _head_ops(m)
    cache = cache_bytes_per_position(m) * (pos0 + tokens)
    rows = tokens * m["hidden_size"] * BF16
    return float(ops), float(_fixed_bytes(m, experts_hit) + cache + rows)
