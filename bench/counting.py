"""Operations and bytes the algorithm needs, from the configuration's sizes.

These are the model's own counts, independent of how the program computes:
a share of a peak is these over the measured time.  Keys are those of the
configuration files (``bench/configs/*.json``).
"""
from __future__ import annotations

BF16 = 2


def matmul_params(c: dict) -> int:
    """Weights that every token multiplies: the layers' projections and the
    (tied) output head.  The embedding lookup is a gather, not a product."""
    D, F = c["hidden_size"], c["intermediate_size"]
    H, KH = c["num_attention_heads"], c["num_key_value_heads"]
    hd = D // H
    attn = D * H * hd + 2 * D * KH * hd + H * hd * D
    mlp = 3 * D * F
    return c["num_hidden_layers"] * (attn + mlp) + c["vocab_size"] * D


def attention_flops(c: dict, q_tokens: int, kv_len: float) -> float:
    """Scores and weighted values of ``q_tokens`` queries, each over
    ``kv_len`` keys, in every layer (2 FLOPs per multiply-add)."""
    D, H = c["hidden_size"], c["num_attention_heads"]
    return 4.0 * c["num_hidden_layers"] * q_tokens * kv_len * H * (D // H)


def train_flops(c: dict, batch: int, seq_len: int) -> float:
    """Forward and backward of one step, recomputation not counted:
    6 per weight per token, plus causal attention (half the score matrix)
    three times over."""
    tokens = batch * seq_len
    return (6.0 * matmul_params(c) * tokens
            + 3.0 * attention_flops(c, tokens, seq_len / 2))


def decode_flops(c: dict, kv_lens) -> float:
    """One decode step of a batch whose slots attend over ``kv_lens`` keys."""
    kv_lens = list(kv_lens)
    return (2.0 * matmul_params(c) * len(kv_lens)
            + sum(attention_flops(c, 1, n) for n in kv_lens))


def decode_attention_bytes(c: dict, kv_lens) -> float:
    """HBM bytes one decode-attention call must move in one layer: the bf16
    keys and values of every live row, the queries and the output."""
    D, H = c["hidden_size"], c["num_attention_heads"]
    KH, hd = c["num_key_value_heads"], D // H
    kv = sum(kv_lens) * KH * hd * BF16 * 2
    return kv + 2 * len(list(kv_lens)) * H * hd * BF16


def decode_attention_flops(c: dict, kv_lens) -> float:
    """FLOPs of one decode-attention call in one layer."""
    return attention_flops(c, 1, 1) / c["num_hidden_layers"] * sum(kv_lens)


def decode_steps(chunks):
    """Per decode step, the cached lengths of the requests it advanced, from
    each chunk's per-request lists of lengths (one entry per step)."""
    for chunk in chunks:
        steps = {}
        for lens in chunk:
            for s, n in enumerate(lens):
                steps.setdefault(s, []).append(n)
        yield from steps.values()
