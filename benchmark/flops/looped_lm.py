"""Operations (and, for the forward kernel's roofline, bytes) of the
looped LM from the configuration's own numbers: a token passes the stack
of `num_hidden_layers` layers `total_ut_steps` times and leaves through
the head at every pass.  A multiply-add is two operations (the convention
of the chip's published peak); recomputation is not counted."""

from __future__ import annotations

from benchmark.flops import attention


def block_applications(config: dict) -> int:
    """Block applications a token: passes x layers."""
    return config["total_ut_steps"] * config["num_hidden_layers"]


def matmul_params(config: dict) -> int:
    """Matrix parameters a token meets, each use counted: attention's four
    projections and the gated MLP's three matrices in every application of
    a block, and the untied head at every exit.  Norms, the one-output
    exit gate and the embedding lookup are not matrix products."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    block = 4 * d * d + 3 * d * ff
    return (block_applications(config) * block
            + config["total_ut_steps"] * config["vocab_size"] * d)


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """6 x matmul parameters met, plus causal attention in every block
    application: scores and values are 2 multiply-adds of width
    hidden_size against, on average, half the sequence's keys, forward
    once and backward twice."""
    attn = (3 * block_applications(config) * 4
            * (traffic["seq_len"] / 2) * config["hidden_size"])
    return 6.0 * matmul_params(config) + attn


def flash_fwd(config: dict, traffic: dict) -> tuple:
    """(operations, bytes) a step of the causal forward kernel:
    `attention.flash_fwd`'s count of one call a layer, over every block
    application, and twice where a block is recomputed in the backward
    pass (its forward kernel runs again)."""
    calls = block_applications(config) * (
        2 if config["model_kwargs"].get("remat") else 1)
    ops, nbytes = attention.flash_fwd({**config, "num_hidden_layers": 1},
                                      traffic)
    return calls * ops, calls * nbytes
