"""Runner for image classifiers trained through `train/step.py`."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.runners.base import Runner, dtype_of, optimizer_of


def build(config: dict, traffic: dict, mesh, reference) -> Runner:
    from cpd_tpu import models
    from cpd_tpu.train import create_train_state, make_train_step

    chips = mesh.devices.size
    batch = traffic["batch_per_chip"] * chips
    size, classes = config["image_size"], config["classes"]
    kwargs = dict(config["model_kwargs"])
    compute_dtype = dtype_of(kwargs.pop("dtype"))
    model = getattr(models, config["model"])(dtype=compute_dtype, **kwargs)
    tx = optimizer_of(config["optimizer"], batch)

    def init_state(key):
        sample = jnp.zeros((2, size, size, 3), compute_dtype)
        return create_train_state(model, tx, sample, key)

    def make_batch(key):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (batch, size, size, 3), compute_dtype)
        y = jax.random.randint(ky, (batch,), 0, classes, jnp.int32)
        return x, y

    def reference_loss(state, x, y):
        # batch statistics are per replica (bn_axis=None), so the global
        # loss is the mean of each chip's own-slice loss
        n, per = chips, x.shape[0] // chips
        parts = [reference(state.params, x[i * per:(i + 1) * per],
                           y[i * per:(i + 1) * per], config)
                 for i in range(n)]
        return sum(parts) / n

    return Runner(init_state=init_state, make_batch=make_batch,
                  step=make_train_step(model, tx, mesh, **traffic["reduce"]),
                  items_per_step=batch, reference_loss=reference_loss)
