"""Operations (and, for the two kernels' rooflines, bytes) of the latent-
attention mixture-of-experts LM as one chip of an expert-parallel group
runs it, from the configuration's own numbers.  A multiply-add is two
operations (the convention of the chip's published peak).  A token meets
`num_experts_per_tok` of `n_routed_experts_published` experts, of which
this chip holds `n_routed_experts`: by expectation it passes
k x held / all routed experts HERE, and the counts take that expectation
(uniform tokens route almost evenly; the step's own counter of the pairs
held says how nearly)."""

from __future__ import annotations


def _width(config: dict) -> int:
    return {"bfloat16": 2, "float32": 4}[config["model_kwargs"]["dtype"]]


def _calls_per_step(config: dict) -> int:
    """Forward passes through a block in one step: a block that is
    recomputed in the backward pass runs its forward kernels twice."""
    return 2 if config["model_kwargs"].get("remat") else 1


def pairs_held_per_layer(config: dict, traffic: dict) -> float:
    """(token, slot) pairs a step routes to the experts held here, by
    expectation, in one expert layer."""
    tokens = traffic["batch_per_chip"] * traffic["seq_len"]
    return (tokens * config["num_experts_per_tok"]
            * config["n_routed_experts"]
            / config["n_routed_experts_published"])


def matmul_params(config: dict) -> float:
    """Matrix parameters a token meets on this chip: attention's four
    projections and the MLP of every layer (a routed layer: router, shared
    expert and its expected share of the routed experts), and the head's
    held rows.  Norms, the selection bias and the embedding lookup are not
    matrix products."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    rank = config["kv_lora_rank"]
    attn = (d * heads * qk + d * (rank + config["qk_rope_head_dim"])
            + rank * heads * (config["qk_nope_head_dim"]
                              + config["v_head_dim"])
            + heads * config["v_head_dim"] * d)
    layers, dense = (config["num_hidden_layers"],
                     config["first_k_dense_replace"])
    expert = 3 * d * config["moe_intermediate_size"]
    met = (config["num_experts_per_tok"] * config["n_routed_experts"]
           / config["n_routed_experts_published"])
    routed_layer = (d * config["n_routed_experts_published"]
                    + config["n_shared_experts"] * expert + met * expert)
    return (layers * attn + dense * 3 * d * config["intermediate_size"]
            + (layers - dense) * routed_layer + config["vocab_size"] * d)


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """6 x matmul parameters, plus causal attention: scores at the q/k
    width and values at the v width against, on average, half the
    sequence's keys, forward once and backward twice.  Recomputation is
    not counted."""
    heads = config["num_attention_heads"]
    widths = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
              + config["v_head_dim"])
    attn = (3 * config["num_hidden_layers"] * 2 * (traffic["seq_len"] / 2)
            * heads * widths)
    return 6.0 * matmul_params(config) + attn


def flash_fwd(config: dict, traffic: dict) -> tuple:
    """(operations, bytes) a step of the causal forward kernel, every call
    of every layer: scores are B x H x S x S x (q/k width) multiply-adds and
    values B x H x S x S x (v width), of which the causal mask keeps half.
    Bytes: q and k at their width, v and o at theirs, in the compute type,
    and the float32 log-sum-exp row.  The real widths, not the padded."""
    b, s = traffic["batch_per_chip"], traffic["seq_len"]
    h = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    dv = config["v_head_dim"]
    calls = config["num_hidden_layers"] * _calls_per_step(config)
    ops = 2 * b * h * s * s * (qk + dv) / 2
    nbytes = _width(config) * b * s * h * (2 * qk + 2 * dv) + 4 * b * h * s
    return calls * ops, calls * nbytes


def experts_all_passes(config: dict, traffic: dict) -> tuple:
    """(operations, bytes) a step of the routed experts' grouped products,
    every expert layer, at the pairs held by expectation: the three
    forward products (each run once a forward pass through the block),
    and for each of them the two backward products (the rows' gradient
    and the weights'), of the same operations each.  The device trace's
    scope paths do not tell a nested scope's backward from its forward
    (PERF.md section 7), so the share is of all passes.  Bytes: the held
    experts' weights once a product, the rows in and out of each, in the
    compute type."""
    d, ff = config["hidden_size"], config["moe_intermediate_size"]
    pairs = pairs_held_per_layer(config, traffic)
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    passes = _calls_per_step(config) + 2
    ops = 2 * 3 * d * ff * pairs
    nbytes = _width(config) * (config["n_routed_experts"] * 3 * d * ff
                               + pairs * 3 * (d + ff))
    return layers * passes * ops, layers * passes * nbytes
