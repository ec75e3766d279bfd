"""Operations and bytes that serving a BitNet decoder needs, from shapes.

The counts are fixed by the work, not by any implementation, so that no
kernel can read over its roofline:

* a projection weight counts at log2(3) bits, the least that any ternary
  packing reads; the tied embedding, used as the head, and the norm gains
  count at bf16 (2 bytes);
* the KV cache counts at bf16: the earlier positions each active slot
  attends, read once, plus the positions it writes;
* a multiply-add counts as 2 operations; attention over ``c`` positions
  counts ``4 * heads * head_dim * c`` per token and layer (scores and
  values);
* a prefill chunk needs logits only for a prompt's last position.
"""
from __future__ import annotations

import math
from typing import Iterable, Tuple

TERNARY_BYTES = math.log2(3) / 8
BF16 = 2


def projection_params(m: dict) -> int:
    """Projection weights of one layer (q, k, v, o and the SwiGLU MLP)."""
    d, hd, ff = m["hidden_size"], m["head_dim"], m["intermediate_size"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff


def kv_bytes_per_token(m: dict) -> int:
    """K and V of one position over every layer, in bf16."""
    return (2 * m["num_key_value_heads"] * m["head_dim"] * BF16
            * m["num_hidden_layers"])


def weight_bytes(m: dict) -> float:
    """Every weight a step reads once: ternary projections, the bf16 head
    (the tied embedding) and the norm gains."""
    layers, d = m["num_hidden_layers"], m["hidden_size"]
    return (projection_params(m) * layers * TERNARY_BYTES
            + m["vocab_size"] * d * BF16 + (2 * layers + 1) * d * BF16)


def _attn_ops(m: dict, context: int) -> int:
    return (4 * m["num_attention_heads"] * m["head_dim"] * context
            * m["num_hidden_layers"])


def _token_ops(m: dict) -> int:
    return 2 * projection_params(m) * m["num_hidden_layers"]


def _head_ops(m: dict) -> int:
    return 2 * m["vocab_size"] * m["hidden_size"]


def decode_step(m: dict, positions: Iterable[int]) -> Tuple[float, float]:
    """(operations, bytes) of one batched decode step; ``positions`` are
    the cache positions each active slot writes (it attends pos + 1)."""
    positions = list(positions)
    ops = sum(_token_ops(m) + _head_ops(m) + _attn_ops(m, p + 1)
              for p in positions)
    kv = kv_bytes_per_token(m) * sum(p + 1 for p in positions)
    embed_rows = len(positions) * m["hidden_size"] * BF16
    return float(ops), float(weight_bytes(m) + kv + embed_rows)


def prefill_chunk(m: dict, tokens: int, pos0: int,
                  last: bool) -> Tuple[float, float]:
    """(operations, bytes) of one prompt chunk of ``tokens`` at ``pos0``."""
    ops = tokens * _token_ops(m)
    ops += sum(_attn_ops(m, pos0 + i + 1) for i in range(tokens))
    if last:
        ops += _head_ops(m)
    kv = kv_bytes_per_token(m) * (pos0 + tokens)
    embed_rows = tokens * m["hidden_size"] * BF16
    return float(ops), float(weight_bytes(m) + kv + embed_rows)


def least_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: operations at the int8 peak
    (W1.58A8 admits int8 arithmetic) or bytes at the HBM bandwidth."""
    return max(ops / peak["int8_ops_per_s"], nbytes / peak["hbm_bytes_per_s"])
