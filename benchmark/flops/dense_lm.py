"""Operations a dense decoder-only LM needs per trained token, from the
configuration's own numbers.  Forward and backward; recomputation is not
counted; a multiply-add is two operations (the convention of the chip's
published peak)."""

from __future__ import annotations


def matmul_params(config: dict) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    blocks' projections and the tied output head.  The embedding lookup is
    not a multiplication; norms are not matrices."""
    d = config["hidden_size"]
    hd = d // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * hd
    ff = config["intermediate_size"]
    block = d * d + d * 2 * kv + d * d + 2 * d * ff   # wq, wkv, wo, mlp
    return config["num_hidden_layers"] * block + config["vocab_size"] * d


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """6 x matmul parameters, plus causal attention: scores and values
    are 2 multiply-adds of width hidden_size against, on average, half
    the sequence's keys, forward once and backward twice."""
    attn = (3 * config["num_hidden_layers"] * 4
            * (traffic["seq_len"] / 2) * config["hidden_size"])
    return 6.0 * matmul_params(config) + attn
