"""Model FLOPs of the decoder, from its shapes alone.

Counts the multiply-adds the model needs (2 FLOPs each): the projections,
the attention products over the keys a causal query really attends to, and
the LM head at the positions whose logits are asked for.  Masked keys,
padding rows and recomputation are not counted.
"""

from __future__ import annotations


def _per_token_matmul(s: dict) -> int:
    d, h, kv, hd, ff = (s["d_model"], s["heads"], s["kv_heads"],
                        s["head_dim"], s["d_ff"])
    proj = d * h * hd * 2 + d * kv * hd * 2       # q, o and k, v
    return 2 * s["layers"] * (proj + 3 * d * ff)


def _attn(s: dict, keys_attended: int) -> int:
    """Scores and weighted values for ``keys_attended`` (query, key) pairs."""
    return 2 * 2 * s["layers"] * s["heads"] * s["head_dim"] * keys_attended


def _head(s: dict, positions: int) -> int:
    return 2 * s["d_model"] * s["vocab"] * positions


def forward(s: dict, batch: int, seq: int, logits_positions: int) -> int:
    """A causal forward pass over ``(batch, seq)`` tokens, with logits at
    ``logits_positions`` positions of each row."""
    pairs = seq * (seq + 1) // 2
    return batch * (seq * _per_token_matmul(s) + _attn(s, pairs)
                    + _head(s, logits_positions))


def served_request(s: dict, prompt_len: int, new_tokens: int) -> int:
    """One request: its prefill (logits at the last position), then one
    decode step for each generated token after the first."""
    total = forward(s, 1, prompt_len, 1)
    for j in range(1, new_tokens):
        ctx = prompt_len + j                       # keys incl. the new one
        total += _per_token_matmul(s) + _attn(s, ctx) + _head(s, 1)
    return total
