"""Runner for hybrid short-convolution / attention mixture-of-experts LMs
(the LFM2 MoE block) trained through `train/lm.py`, as one chip of an
expert-parallel group holds them.

The configuration's file carries the published `config.json` keys; this
maps those that `train_lm` does not know onto `models.conv_moe_lm`'s
arguments and hands the rest to `train_lm`, so that the step, the
optimizer, the batches and the reference's gradient are the other LM
cells' own.  `num_experts` is the number of experts this chip holds, from
`expert_first`; `num_experts_published` the router's width.  The model has
no bias terms, renormalises the selected gates and always has the
selection bias: a file that says otherwise is refused.
"""

from __future__ import annotations

from benchmark.runners import train_lm

# what the model computes, whatever the file says
_FIXED = {"conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True}


def model_kwargs(config: dict) -> dict:
    other = {k: config[k] for k in _FIXED if config[k] != _FIXED[k]}
    if other:
        raise ValueError(f"the model computes {_FIXED}, not {other}")
    return dict(layer_types=config["layer_types"],
                first_dense=config["num_dense_layers"],
                l_cache=config["conv_L_cache"],
                rope_theta=float(config["rope_parameters"]["rope_theta"]),
                eps=config["norm_eps"],
                n_experts=config["num_experts_published"],
                experts_held=config["num_experts"],
                expert_first=config.get("expert_first", 0),
                top_k=config["num_experts_per_tok"],
                moe_d_ff=config["moe_intermediate_size"],
                routed_scaling=config["routed_scaling_factor"],
                init_std=config["initializer_range"])


def build(config: dict, traffic: dict, mesh, reference):
    extra = {**config["model_kwargs"], **model_kwargs(config)}
    return train_lm.build({**config, "model_kwargs": extra}, traffic, mesh,
                          reference)
