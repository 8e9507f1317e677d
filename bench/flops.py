"""Operations and bytes that the work needs, from shapes alone.

These count what the algorithm needs, whatever implements it, so that a
share of a peak cannot rise because an implementation does less of it.
"""
from __future__ import annotations


def lm_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through, output head included (the
    embedding lookup is not a product)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def lm_attention_flops(cfg: dict, context: int) -> int:
    """Scores and weighted values of one token over ``context`` keys, in
    every layer: 2 x 2 x heads x head_dim x context."""
    return (4 * cfg["num_attention_heads"] * cfg["head_dim"] * context
            * cfg["num_hidden_layers"])


def lm_token_flops(cfg: dict, context: int) -> int:
    """Forward FLOPs of one token that attends over ``context`` positions
    (itself included)."""
    return 2 * lm_matmul_params(cfg) + lm_attention_flops(cfg, context)


def lm_request_flops(cfg: dict, prompt: int, gen: int) -> int:
    """A served request: the prompt's tokens in prefill (contexts 1..n),
    then ``gen - 1`` decode steps (contexts n+1..n+gen-1); the first
    token comes from the prefill."""
    tokens = prompt + max(gen - 1, 0)
    contexts = tokens * (tokens + 1) // 2          # 1 + 2 + ... + tokens
    return (2 * lm_matmul_params(cfg) * tokens
            + lm_attention_flops(cfg, 1) * contexts)
