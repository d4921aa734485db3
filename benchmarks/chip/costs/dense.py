"""Operations and bytes that a dense decoder LM's algorithm needs, from
its published sizes and the positions it runs at.  Counted once per
call; what an implementation recomputes or re-reads does not count.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

if TYPE_CHECKING:
    from families.dense import Spec


def layer_matmul_params(s: Spec) -> int:
    """Weights of one layer that enter a matmul."""
    return (s.d_model * s.heads * s.head_dim            # w_q
            + 2 * s.d_model * s.kv_heads * s.head_dim   # w_k, w_v
            + s.heads * s.head_dim * s.d_model          # w_o
            + 3 * s.d_model * s.d_ff)                   # gate, up, down


def norm_params(s: Spec) -> int:
    per = s.d_model * (1 if s.norm == "rms" else 2)
    return (2 * s.layers + 1) * per


def params(s: Spec) -> int:
    """Every parameter of the model."""
    head = 0 if s.tied else s.d_model * s.vocab
    return (s.vocab * s.d_model + head + s.layers * layer_matmul_params(s)
            + norm_params(s))


def matmul_params(s: Spec) -> int:
    """Weights that a token multiplies: layers plus the output head (the
    embedding lookup is a gather)."""
    return s.layers * layer_matmul_params(s) + s.d_model * s.vocab


def weight_bytes(s: Spec) -> int:
    """Weights one decode step reads: bf16 matrices and head, f32 norms;
    the embedding table only where it is also the head."""
    return 2 * matmul_params(s) + 4 * norm_params(s)


def attn_flops(s: Spec, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys."""
    return 4 * s.layers * s.heads * s.head_dim * context


def decode_tokens_flops(s: Spec, positions: list[tuple[int, int]]) -> int:
    """Forward operations of decoding every position in each ``(lo, hi)``
    range (inclusive); position ``p`` attends to ``p + 1`` keys."""
    n = sum(hi - lo + 1 for lo, hi in positions)
    ctx = sum((lo + hi + 2) * (hi - lo + 1) // 2 for lo, hi in positions)
    return 2 * matmul_params(s) * n + attn_flops(s, 1) * ctx


def decode_bytes(s: Spec, steps: int, positions: list[tuple[int, int]]
                 ) -> int:
    """Bytes ``steps`` decode steps need: the weights once per step, the
    filled K/V prefix of each position decoded, and its new K/V."""
    n = sum(hi - lo + 1 for lo, hi in positions)
    ctx = sum((lo + hi + 2) * (hi - lo + 1) // 2 for lo, hi in positions)
    return (steps * weight_bytes(s) + ctx * s.kv_bytes_per_token
            + n * s.kv_bytes_per_token)


def prefill_flops(s: Spec, prompt_len: int) -> int:
    """Forward operations of a prompt under causal attention."""
    return decode_tokens_flops(s, [(0, prompt_len - 1)])


def train_flops_per_token(s: Spec, seq: int) -> int:
    """Forward and backward operations per trained token at sequence
    length ``seq``, causal attention (a query sees (seq + 1) / 2 keys on
    average), recomputation not counted."""
    return 3 * (2 * matmul_params(s) + attn_flops(s, 1) * (seq + 1) // 2)
