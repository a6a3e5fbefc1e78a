"""Runner for decoder-only LMs trained through `train/lm.py`.

The configuration's file carries the published `config.json` keys; this
maps them onto `models.transformer_lm`'s arguments.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.runners.base import (Runner, dtype_of, first_gradient_of,
                                    optimizer_of)


def model_kwargs(config: dict) -> dict:
    return dict(vocab_size=config["vocab_size"],
                d_model=config["hidden_size"],
                n_layers=config["num_hidden_layers"],
                n_heads=config["num_attention_heads"],
                n_kv_heads=config["num_key_value_heads"],
                d_ff=config["intermediate_size"])


def build(config: dict, traffic: dict, mesh, reference) -> Runner:
    from cpd_tpu import models
    from cpd_tpu.train import make_lm_train_step
    from cpd_tpu.train.state import TrainState

    chips = mesh.devices.size
    batch, seq = traffic["batch_per_chip"] * chips, traffic["seq_len"]
    vocab = config["vocab_size"]
    extra = dict(config["model_kwargs"])
    compute_dtype = dtype_of(extra.pop("dtype"))
    factory = getattr(models, config["model"])
    model = factory(**model_kwargs(config), dtype=compute_dtype, **extra)
    # parameter shapes do not depend on the attention path or the length:
    # initialise through the plain path on 8 tokens, not the kernel on 4,096
    init_model = factory(**model_kwargs(config), dtype=compute_dtype,
                         **{**extra, "attn_impl": "xla"})
    tx = optimizer_of(config["optimizer"], batch * seq)

    def init_state(key):
        params = init_model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        return TrainState(step=jnp.zeros([], jnp.int32), params=params,
                          batch_stats={}, opt_state=tx.init(params))

    def make_batch(key):
        tokens = jax.random.randint(key, (batch, seq + 1), 0, vocab,
                                    jnp.int32)
        return tokens[:, :-1], tokens[:, 1:]

    def reference_loss(state, tokens, targets):
        return reference(state.params, tokens, targets, config)

    def reference_grad(params, tokens, targets):
        return jax.value_and_grad(reference)(params, tokens, targets, config)

    return Runner(init_state=init_state, make_batch=make_batch,
                  step=make_lm_train_step(model, tx, mesh,
                                          **traffic["reduce"]),
                  items_per_step=batch * seq, reference_loss=reference_loss,
                  reference_grad=reference_grad,
                  first_gradient=first_gradient_of(config["optimizer"]))
