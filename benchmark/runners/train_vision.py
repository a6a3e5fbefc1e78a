"""Runner for image classifiers trained through `train/step.py`."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmark.runners.base import (Runner, dtype_of, first_gradient_of,
                                    optimizer_of)


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

    last_scale = config["residual_last_bn_scale"]

    def init_state(key):
        sample = jnp.zeros((2, size, size, 3), compute_dtype)
        state = create_train_state(model, tx, sample, key)
        # each residual branch's last batch-norm scale (Goyal et al. 2017
        # and torchvision's `zero_init_residual` start it at 0): with
        # every scale at 1 the gradient at initialisation is too ill
        # conditioned for the check of the first updates (README.md)
        params = {
            name: ({**part, "bn3": {**part["bn3"], "scale":
                                    last_scale * part["bn3"]["scale"]}}
                   if "bn3" in part else part)
            for name, part in state.params.items()}
        return state.replace(params=params)

    def make_batch(key):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (batch, size, size, 3), compute_dtype)
        y = jax.random.randint(ky, (batch,), 0, classes, jnp.int32)
        return x, y

    def loss_of(params, x, y):
        # batch statistics are per replica (bn_axis=None), so the global
        # loss is the mean of each chip's own-slice loss
        n, per = chips, x.shape[0] // chips
        parts = [reference(params, x[i * per:(i + 1) * per],
                           y[i * per:(i + 1) * per], config)
                 for i in range(n)]
        return sum(parts) / n

    def reference_loss(state, x, y):
        return loss_of(state.params, x, y)

    def slice_grad(params, x, y):
        """A chip's own-slice loss and gradient, averaged over the chips:
        `jax.value_and_grad(loss_of)` with each slice's backward pass on
        the chip that holds the slice (left to XLA's partitioner, the four
        slices' passes need 12.4 GiB of temporaries a chip, not 5.7).
        `check_vma=False`: the gradient of a replicated tree stays the
        chip's own until the `pmean`."""
        value, grad = jax.value_and_grad(reference)(params, x, y, config)
        return jax.lax.pmean((value, grad), "dp")

    reference_grad = (jax.value_and_grad(loss_of) if chips == 1 else
                      jax.shard_map(slice_grad, mesh=mesh,
                                    in_specs=(P(), P("dp"), P("dp")),
                                    out_specs=(P(), P()), check_vma=False))

    return Runner(init_state=init_state, make_batch=make_batch,
                  step=make_train_step(model, tx, mesh, **traffic["reduce"]),
                  items_per_step=batch, reference_loss=reference_loss,
                  reference_grad=reference_grad,
                  first_gradient=first_gradient_of(config["optimizer"]))
