"""What a recomputed block keeps of `flash_gqa`: the kernel's output and
log-sum-exp (`ops/flash_gqa.py:KEEP_FLASH_RESIDUALS`), held against the
bare `nn.remat` that keeps nothing.  Each model that recomputes its
blocks calls `compare_with_bare_remat` from its own test file.
"""

from __future__ import annotations

import re

import jax
import numpy as np
from jax.extend import core as jex

from cpd_tpu.obs import scopes


def kernel_calls(jaxpr, scope: str) -> int:
    """Pallas calls named after `scope` in a jaxpr, nested ones too."""
    name = scopes.kernel_name(scope)
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            count += eqn.params["name"] == name
            continue
        for sub in jax.tree.leaves(
                list(eqn.params.values()),
                is_leaf=lambda v: isinstance(v, (jex.Jaxpr, jex.ClosedJaxpr))):
            if isinstance(sub, (jex.Jaxpr, jex.ClosedJaxpr)):
                count += kernel_calls(getattr(sub, "jaxpr", sub), scope)
    return count


def _gradient(loss, params):
    """(the gradient's jaxpr, its text without the remat policies, the
    gradient)."""
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    text = re.sub(r"policy=[^\n]*", "policy=", str(jaxpr))
    return jaxpr.jaxpr, text, jax.jit(jax.grad(loss))(params)


def compare_with_bare_remat(monkeypatch, module, loss, params,
                            attention_blocks: int) -> None:
    """`loss(params)` applies a model of `module` that recomputes its
    blocks.  Against the same model with a bare `nn.remat` (the module's
    policy set to None): the forward kernel runs once per block with
    attention where it ran twice, the backward kernels as often, and the
    gradient is the same to 1e-6 of each leaf's largest element (bit for
    bit where it can be).  A model without the kernels
    (`attention_blocks` 0) traces to the same jaxpr either way."""
    kept, kept_text, g_kept = _gradient(loss, params)
    with monkeypatch.context() as m:
        m.setattr(module, "KEEP_FLASH_RESIDUALS", None)
        bare, bare_text, g_bare = _gradient(loss, params)

    fwd = scopes.KERNEL_FLASH_GQA_FWD
    assert kernel_calls(kept, fwd) == attention_blocks
    assert kernel_calls(bare, fwd) == 2 * attention_blocks
    for bwd in (scopes.KERNEL_FLASH_GQA_BWD_DQ,
                scopes.KERNEL_FLASH_GQA_BWD_DKV):
        assert (kernel_calls(kept, bwd) == kernel_calls(bare, bwd)
                == attention_blocks)
    if not attention_blocks:
        assert kept_text == bare_text
    for x, y in zip(jax.tree.leaves(g_kept), jax.tree.leaves(g_bare),
                    strict=True):
        x, y = np.asarray(x), np.asarray(y)
        assert np.array_equal(x, y) or (
            np.abs(x - y).max() <= 1e-6 * np.abs(y).max())
