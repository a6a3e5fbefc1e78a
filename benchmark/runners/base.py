"""What a runner hands the harness, and the two helpers both runners share.

A runner is a module `benchmark/runners/<name>.py` with one function
`build(config, traffic, mesh, reference) -> Runner`; the configuration's
`runner` key names it, and `reference` is the configuration's plain
float32 loss (`benchmark/reference/`).  Everything in a `Runner` is a pure function of its arguments, so
the harness can `jax.jit` it (weights and batches are made on the device,
each in one call) and the compile rehearsal can `jax.eval_shape` it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Runner:
    init_state: Callable[[Any], Any]        # key -> TrainState
    make_batch: Callable[[Any], tuple]      # key -> (a, b), global batch
    step: Callable[[Any, Any, Any], tuple]  # (state, a, b) -> (state, metrics)
    items_per_step: int                     # over all chips
    reference_loss: Callable[[Any, Any, Any], Any]
    # (state, a, b) -> the loss `step` reports for that batch, by the
    # configuration's plain float32 reference
    reference_grad: Callable[[Any, Any, Any], tuple]
    # (params, a, b) -> (that loss, its float32 gradient), `jax.grad` of
    # the reference at `highest` matmul precision: what `benchmark/check.py`
    # steps with the configuration's optimizer in plain arithmetic
    first_gradient: Callable[[Any, Any], Any]
    # (params before a state's first step, the state after it) -> the
    # gradient as the optimizer got it, read out of the optimizer's state


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def optimizer_of(spec: dict, global_items: int):
    """The configuration's optimizer at a constant learning rate: `lr`, or
    `lr_per_256_items` scaled by the global batch (Goyal et al.'s rule,
    which the source's trainer applies)."""
    from cpd_tpu.train import make_optimizer

    lr = spec["lr"] if "lr" in spec else (
        spec["lr_per_256_items"] * global_items / 256.0)
    return make_optimizer(spec["name"], lambda step: lr,
                          momentum=spec["momentum"],
                          weight_decay=spec["weight_decay"])


def first_gradient_of(spec: dict):
    """`Runner.first_gradient` for the optimizers `optimizer_of` builds.
    After one step from a zero buffer `cpd_tpu.train.optim.sgd`'s momentum
    buffer is the gradient plus the weight decay's term, element for
    element (the update is it times the learning rate, rounded into the
    master weights, so the parameters themselves say less)."""
    if spec["name"] != "sgd":
        raise KeyError(f"no first_gradient for optimizer {spec['name']!r}")
    wd = spec["weight_decay"]

    def first_gradient(params, state):
        return jax.tree.map(lambda buf, w: buf - wd * w if wd else buf,
                            state.opt_state.momentum_buf, params)

    return first_gradient
