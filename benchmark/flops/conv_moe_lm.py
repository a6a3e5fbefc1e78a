"""Operations (and, for the rooflines, bytes) of the hybrid
short-convolution / attention mixture-of-experts LM as one chip of an
expert-parallel group runs it, from the configuration's own numbers.  A
multiply-add is two operations (the convention of the chip's published
peak); recomputation is not counted in the step's total.  A token meets
`num_experts_per_tok` of `num_experts_published` experts, of which this
chip holds `num_experts`: by expectation it passes k x held / all routed
experts HERE (uniform tokens route almost evenly; the step's own counter
of the pairs held says how nearly)."""

from __future__ import annotations

from benchmark.flops import attention


def _width(config: dict) -> int:
    return {"bfloat16": 2, "float32": 4}[config["model_kwargs"]["dtype"]]


def _calls_per_step(config: dict) -> int:
    """Forward passes through a block in one step: a block that is
    recomputed in the backward pass runs its forward twice."""
    return 2 if config["model_kwargs"].get("remat") else 1


def _layers(config: dict, mixer: str) -> int:
    return config["layer_types"].count(mixer)


def _head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def matmul_params(config: dict) -> float:
    """Matrix parameters a token meets on this chip: a conv mixer's in and
    out projections (d -> 3d, d -> d), attention's four projections, the
    dense MLPs, a routed layer's router and its expected share of the held
    experts, and the tied head's held rows.  Norms, the convolution's taps,
    the gating products, the selection bias and the embedding lookup are
    not matrix products."""
    d = config["hidden_size"]
    hd = _head_dim(config)
    conv = 3 * d * d + d * d
    attn = 2 * d * hd * (config["num_attention_heads"]
                         + config["num_key_value_heads"])
    dense = config["num_dense_layers"]
    met = (config["num_experts_per_tok"] * config["num_experts"]
           / config["num_experts_published"])
    routed = (d * config["num_experts_published"]
              + met * 3 * d * config["moe_intermediate_size"])
    return (_layers(config, "conv") * conv
            + _layers(config, "full_attention") * attn
            + dense * 3 * d * config["intermediate_size"]
            + (len(config["layer_types"]) - dense) * routed
            + config["vocab_size"] * d)


def forward_flops_per_token(config: dict, traffic: dict) -> float:
    """2 x matmul parameters, plus causal attention in every attention
    layer: scores and values are 2 multiply-adds of the query heads' width
    against, on average, half the sequence's keys."""
    core = (_layers(config, "full_attention") * 2 * 2
            * config["num_attention_heads"] * _head_dim(config)
            * traffic["seq_len"] / 2)
    return 2.0 * matmul_params(config) + core


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Forward once and backward twice."""
    return 3.0 * forward_flops_per_token(config, traffic)


def conv_mixer_all_passes(config: dict, traffic: dict) -> tuple:
    """(operations, bytes) a step of the conv mixers' two projections in
    every pass: the forward (once a forward pass through the block: twice
    where the block is recomputed) and, for each product, the two backward
    products (the rows' gradient and the weights'), of the same operations
    each.  The device trace's scope paths do not tell a nested scope's
    backward from its forward (PERF.md section 7), so the share is of all
    passes.  The gating products, the taps and the norm are elementwise
    and not counted.  Bytes: each product's weights once and its rows in
    and out, in the compute type."""
    d = config["hidden_size"]
    tokens = traffic["batch_per_chip"] * traffic["seq_len"]
    passes = _layers(config, "conv") * (_calls_per_step(config) + 2)
    ops = 2 * tokens * (3 * d * d + d * d)
    nbytes = _width(config) * (3 * d * d + d * d
                               + tokens * ((d + 3 * d) + (d + d)))
    return passes * ops, passes * nbytes


def flash_fwd(config: dict, traffic: dict) -> tuple:
    """(operations, bytes) a step of the causal forward kernel:
    `attention.flash_fwd`'s count of one call a layer at the heads' real
    width (not the 128 lanes the kernel pads a head to), over every
    attention layer, twice where a block is recomputed in the backward
    pass (its forward kernel runs again)."""
    ops, nbytes = attention.flash_fwd(
        {**config, "num_hidden_layers": _layers(config, "full_attention")},
        traffic)
    calls = _calls_per_step(config)
    return calls * ops, calls * nbytes
