"""Cluster-size emulation ("emulate node") — N virtual nodes per real device.

TPU-native re-implementation of the reference's `--emulate_node` mechanism
(reference: example/ResNet18/tools/mix.py:224-285, example/ResNet50/
main.py:156-202): each real process runs N micro-batches, buffers per-param
gradients, then performs a *local* APS shift + quantize + ordered quantized
accumulation — "as we use a single node to emulate multi-node, we should
first accumulate gradients within a single node and then communicate them"
(mix.py:275-277) — before the cross-process `sum_gradients`.

Here the micro-batch loop is vectorized: the trainer computes per-micro-batch
grads with `jax.vmap`/`lax.scan` (leaf shape ``(N, *shape)``) and this module
reduces the leading axis with the same ordered primitives as the collectives,
so emulated-node numerics are bit-identical to the reference's recipe.

Faithful quirks preserved (mix.py:251-282):
* N == 1 shortcut: the single grad is used as-is, NO quantization
  (mix.py:254-256).
* The quantize step runs even when APS is off (shift is just 0)
  (mix.py:267-271: `shift_factor = 0 if not use_APS`, quantize regardless).
* All-zero guard: max_exp == -100 sentinel → shift 0 (mix.py:267-268).
* The local shift uses only the *local* micro-batch max — the global pmax
  happens later inside `sum_gradients`.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..obs import scopes
from ..quant.numerics import cast_to_format, cast_to_format_sr
from .aps import aps_max_exponents, aps_shift_factors, exp2_exact
from .reduction import ordered_quantized_sum

__all__ = ["emulate_node_reduce", "reduce_stacked_leaf",
           "make_overlap_emulate_fn"]


def _reduce_leaf(g: jnp.ndarray, n: int, use_aps: bool,
                 grad_exp: int, grad_man: int, key=None) -> jnp.ndarray:
    """Reduce one stacked leaf (N, *shape) -> (*shape,)."""
    if n == 1:
        return g[0]  # mix.py:254-256 — no quantization for a single grad
    if use_aps:
        max_exp = aps_max_exponents([g], n)
        shift = aps_shift_factors(max_exp, grad_exp)[0]
    else:
        shift = jnp.float32(0.0)  # quantize still runs (mix.py:267-271)
    scale = exp2_exact(shift)
    if key is None:
        g = cast_to_format(g * scale, grad_exp, grad_man)
        res = ordered_quantized_sum(g, grad_exp, grad_man)
    else:
        k_pre, k_sum = jax.random.split(key)
        g = cast_to_format_sr(g * scale, grad_exp, grad_man, k_pre)
        res = ordered_quantized_sum(g, grad_exp, grad_man, key=k_sum)
    return res / exp2_exact(shift)  # true divide, as mix.py:280 does


def reduce_stacked_leaf(g: jnp.ndarray, n: int, use_aps: bool = False,
                        grad_exp: int = 5, grad_man: int = 2,
                        key=None) -> jnp.ndarray:
    """Public per-leaf emulate-node reduce: one stacked (N, *shape) leaf
    -> its locally-accumulated (*shape,) gradient, with EXACTLY
    `emulate_node_reduce`'s per-leaf semantics (N==1 shortcut, quantize
    even without APS, local-max shift).

    For callers that reduce one leaf at a time — the overlapped
    backward-reduce taps (parallel/overlap.py `emulate_reduce` hook,
    ISSUE 12), whose bwd rules see a single leaf's cotangent.  The SR
    `key` must already be folded by the leaf's GLOBAL tree index
    (`fold_in(emu_key, leaf_index)`) to reproduce
    `emulate_node_reduce`'s per-leaf streams bit for bit."""
    return _reduce_leaf(g, n, use_aps, grad_exp, grad_man, key=key)


def make_overlap_emulate_fn(n: int, use_aps: bool, grad_exp: int,
                            grad_man: int, sr: bool):
    """The `overlapped_grads(emulate_reduce=...)` hook body of the
    gradient stage (train/grads.py), kept beside `emulate_node_reduce`
    so the SR-key contract — `fold_in(emu_key, GLOBAL leaf index)`
    feeding `reduce_stacked_leaf`, exactly `emulate_node_reduce`'s
    per-leaf streams — is read in one file.

    Returns ``fn(cotangent, extra, leaf_index, emu_key)``: stacks the
    LAST micro-batch's cotangent under the prior micro-batches' stacked
    gradients (`extra`, (N-1, *leaf)) and runs the rank-local
    emulate-node ordered reduce on the (N, *leaf) result."""

    @jax.named_scope(scopes.EMULATE_NODE)
    def emulate_fn(g, extra, i, ekey):
        stacked_leaf = jnp.concatenate([extra, g[None]], 0)
        return reduce_stacked_leaf(
            stacked_leaf, n, use_aps, grad_exp, grad_man,
            key=(jax.random.fold_in(ekey, i) if sr else None))

    return emulate_fn


@jax.named_scope(scopes.EMULATE_NODE)
def emulate_node_reduce(stacked_grads: Any, emulate_node: int,
                        use_aps: bool = False, grad_exp: int = 5,
                        grad_man: int = 2, key=None,
                        rounding: str = "nearest") -> Any:
    """Locally reduce N stacked micro-batch gradients per leaf.

    stacked_grads: pytree with leaves shaped (emulate_node, *param_shape).
    Returns the locally-accumulated gradient pytree (leaf shape
    (*param_shape,)), ready for the cross-device `sum_gradients`.

    rounding='stochastic' with `key` (beyond-reference) switches every
    cast — the local pre-quantize and each ordered-accumulation step — to
    unbiased stochastic rounding, one independent bitstream per leaf.
    The key/rounding contract matches `sum_gradients`: a key with
    'nearest' raises (it would be silently ignored), 'stochastic' without
    a key raises."""
    if rounding not in ("nearest", "stochastic"):
        raise ValueError(f"unknown rounding {rounding!r}")
    if rounding == "stochastic" and key is None:
        raise ValueError("rounding='stochastic' requires a PRNG key")
    if rounding == "nearest" and key is not None:
        raise ValueError("a PRNG key was passed but rounding='nearest' "
                         "would ignore it; pass rounding='stochastic' "
                         "(matching sum_gradients' contract)")
    if key is None:
        return jax.tree.map(
            lambda g: _reduce_leaf(g, emulate_node, use_aps, grad_exp,
                                   grad_man),
            stacked_grads)
    leaves, treedef = jax.tree_util.tree_flatten(stacked_grads)
    out = [_reduce_leaf(g, emulate_node, use_aps, grad_exp, grad_man,
                        key=jax.random.fold_in(key, i))
           for i, g in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)
