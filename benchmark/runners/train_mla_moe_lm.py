"""Runner for latent-attention mixture-of-experts LMs (the DeepSeek-V3
block) trained through `train/lm.py`, as one chip of an expert-parallel
group holds them.

The configuration's file carries the published `config.json` keys; this
maps those that `train_lm` does not know onto `models.mla_moe_lm`'s
arguments and hands the rest to `train_lm`, so that the step, the
optimizer, the batches and the reference's gradient are the dense LM
cells' own.  `n_routed_experts` is the number of experts this chip holds,
from `expert_first`; `n_routed_experts_published` the router's width.
"""

from __future__ import annotations

from benchmark.runners import train_lm


def model_kwargs(config: dict) -> dict:
    return dict(kv_lora_rank=config["kv_lora_rank"],
                qk_nope_dim=config["qk_nope_head_dim"],
                qk_rope_dim=config["qk_rope_head_dim"],
                v_head_dim=config["v_head_dim"],
                rope_theta=float(config["rope_theta"]),
                eps=config["rms_norm_eps"],
                first_dense=config["first_k_dense_replace"],
                n_experts=config["n_routed_experts_published"],
                experts_held=config["n_routed_experts"],
                expert_first=config.get("expert_first", 0),
                top_k=config["num_experts_per_tok"],
                moe_d_ff=config["moe_intermediate_size"],
                n_shared_experts=config["n_shared_experts"],
                routed_scaling=config["routed_scaling_factor"],
                init_std=config["initializer_range"])


def build(config: dict, traffic: dict, mesh, reference):
    extra = {**config["model_kwargs"], **model_kwargs(config)}
    return train_lm.build({**config, "model_kwargs": extra}, traffic, mesh,
                          reference)
