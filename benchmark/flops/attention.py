"""Operations and bytes of the attention kernels per step and chip, from
the cell's own sizes.  A multiply-add is two operations."""

from __future__ import annotations


def flash_fwd(config: dict, traffic: dict) -> tuple:
    """The causal forward kernel, one call a layer: scores and values are
    two matrix products of B x Hq x S x S x D multiply-adds each, of which
    the causal mask keeps half.  Bytes: q and o (Hq heads), k and v (Hkv
    heads) in the compute type, and the float32 log-sum-exp row."""
    b, s = traffic["batch_per_chip"], traffic["seq_len"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // hq
    layers = config["num_hidden_layers"]
    width = {"bfloat16": 2, "float32": 4}[config["model_kwargs"]["dtype"]]
    ops = 2 * 2 * b * hq * s * s * d / 2
    nbytes = width * b * s * d * (2 * hq + 2 * hkv) + 4 * b * hq * s
    return layers * ops, layers * nbytes
