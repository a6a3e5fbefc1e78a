"""What a runner hands the harness, and the two helpers both runners share.

A runner is a module `benchmark/runners/<name>.py` with one function
`build(config, traffic, mesh) -> Runner`; the configuration's `runner` key
names it.  Everything in a `Runner` is a pure function of its arguments, so
the harness can `jax.jit` it (weights and batches are made on the device,
each in one call) and the compile rehearsal can `jax.eval_shape` it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

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
