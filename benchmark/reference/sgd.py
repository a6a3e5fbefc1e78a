"""Plain SGD with momentum as `torch.optim.SGD` steps it (He et al.'s and
the source trainers' optimizer): the step the configuration's `optimizer`
entry describes, in straightforward arithmetic on float32 trees.  It
shares no code with `cpd_tpu`; `benchmark/check.py` steps the reference
with it, and a test holds it to `cpd_tpu.train.make_optimizer("sgd")`.

    d      = g + weight_decay * w       (every leaf: the runners give no mask)
    buf    = momentum * buf + d         (buf is zero before the first step)
    update = -lr * buf                  (w + update is the stepped weight)
"""

from __future__ import annotations

import jax


def learning_rate(spec: dict, global_items: int) -> float:
    """Constant: `lr`, or `lr_per_256_items` x global batch / 256 (Goyal
    et al.'s rule), as `runners/base.py:optimizer_of` hands it to the
    program."""
    if "lr" in spec:
        return spec["lr"]
    return spec["lr_per_256_items"] * global_items / 256.0


def init(params):
    """The optimizer's state before the first step: a zero buffer."""
    return jax.tree.map(lambda w: 0.0 * w, params)


def update(params, buf, grads, spec: dict, lr: float):
    """One step: `(params, buf, grads) -> (what to add to params, buf)`."""
    wd, mu = spec["weight_decay"], spec["momentum"]

    def one(w, b, g):
        d = g + wd * w if wd else g
        b = mu * b + d
        return -lr * b, b

    out = jax.tree.map(one, params, buf, grads)
    pick = lambda i: jax.tree.map(lambda w, o: o[i], params, out)
    return pick(0), pick(1)
