"""MoE LM train step over a ("dp", "ep") mesh — expert parallelism.

Companion of train/lm.py (tp/sp) and train/pp.py (pp) for the `ep` axis.
Tokens are sharded over BOTH dp and ep (ep doubles as a data axis outside
the expert dispatch); expert weight stacks are ep-sharded; router /
attention / norm params are replicated over ep.

Gradient flow: expert-stack grads are complete on their owner rank (the
all_to_all transpose routes cotangents back to the token's home rank);
replicated params get a `psum` over ep; then the quantized dp
`sum_gradients` (APS / ordered / Kahan) and a shard-local elementwise
optimizer update (LARS refused, same argument as train/lm.py).

The Switch load-balancing auxiliary loss (sown by MoEFeedForward) is
collected per block and added with weight `aux_weight` — without it top-1
routing degenerates to one hot expert.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..models.moe import MoETransformerLM, moe_param_specs
from ..compat import shard_map
from .grads import ReduceOptions, reduce_local
from .state import (TrainState, make_sharded_stepper, reject_norm_based,
                    state_specs_like)

__all__ = ["make_moe_train_step", "make_moe_eval_step", "moe_state_specs"]


def moe_state_specs(state: TrainState, ep_axis: str = "ep") -> TrainState:
    return state_specs_like(state, moe_param_specs(state.params, ep_axis))


def make_moe_train_step(model: MoETransformerLM,
                        tx: optax.GradientTransformation, mesh: Mesh, *,
                        axis_dp: str = "dp", axis_ep: str = "ep",
                        aux_weight: float = 0.01, use_aps: bool = False,
                        grad_exp: int = 8, grad_man: int = 23,
                        use_kahan: bool = False, mode: str = "faithful",
                        grad_rounding: str = "nearest", grad_seed: int = 0,
                        donate: bool = True):
    """Build jitted ``(state, tokens, targets) -> (state, metrics)``.

    tokens/targets: (global_batch, T) int32 sharded over (dp, ep).

    use_aps ... grad_seed are the seven of `train.grads.ReduceOptions`'
    fields this builder takes (described there; the rest keep their
    defaults).  Its own about grad_rounding='stochastic': the stage's key
    depends only on (grad_seed, step), so it is identical across ep,
    which is required for replicated leaves (their post-ep-psum grads
    are identical on every ep copy and must round identically) and
    harmless for expert stacks (ep ranks own disjoint experts, nothing
    sums across ep)."""
    opts = ReduceOptions(use_aps=use_aps, grad_exp=grad_exp,
                         grad_man=grad_man, use_kahan=use_kahan, mode=mode,
                         grad_rounding=grad_rounding,
                         grad_seed=grad_seed).check()
    reject_norm_based(tx, "ep-sharded step")
    data_axes = (axis_dp, axis_ep)

    def step_fn(state: TrainState, tokens, targets):
        def loss_of(params, toks, tgts):
            logits, mut = model.apply({"params": params}, toks, train=True,
                                      mutable=["intermediates"])
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, tgts)
            local_sum = ce.sum()
            local_n = jnp.float32(ce.size)
            global_n = lax.psum(local_n, data_axes)
            aux = jnp.sum(jnp.stack(jax.tree.leaves(
                mut["intermediates"]))) if aux_weight else jnp.float32(0.0)
            # normalize the aux term by the number of contributing ranks:
            # every dp x ep rank adds its own copy and the dp reduction
            # SUMS gradients, so without /world the aux gradient would
            # scale with device count while CE stays world-invariant
            world = lax.psum(jnp.float32(1.0), data_axes)
            loss = local_sum / global_n + aux_weight * aux / world
            hits = jnp.sum(jnp.argmax(logits, -1) == tgts)
            return loss, (local_sum, local_n, hits)

        (_, (lsum, ln, hits)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(state.params, tokens, targets)

        # replicated params: finish the ep sum; expert stacks (spec names
        # the ep axis) are complete on their owner rank
        specs = moe_param_specs(state.params, axis_ep)
        grads = jax.tree.map(
            lambda g, s: g if axis_ep in tuple(
                a for a in s if a is not None) else lax.psum(g, axis_ep),
            grads, specs, is_leaf=lambda x: isinstance(x, P))
        grads, _ = reduce_local(grads, step=state.step, axis_dp=axis_dp,
                                opts=opts)

        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(step=state.step + 1, params=new_params,
                               batch_stats=state.batch_stats,
                               opt_state=new_opt)
        from ..resilience.guard import guard_metrics
        total = lax.psum(ln, data_axes)
        metrics = {
            **guard_metrics(new_opt),
            "loss": lax.psum(lsum, data_axes) / total,
            "accuracy": lax.psum(hits.astype(jnp.float32),
                                 data_axes) / total,
        }
        return new_state, metrics

    return make_sharded_stepper(
        step_fn, lambda s: moe_state_specs(s, axis_ep), mesh,
        P(data_axes), donate=donate)


def make_moe_eval_step(model: MoETransformerLM, mesh: Mesh, *,
                       axis_dp: str = "dp", axis_ep: str = "ep"):
    """Jitted ``(state, tokens, targets) -> {'loss','accuracy'}`` over the
    same (dp, ep) token sharding as the train step."""
    data_axes = (axis_dp, axis_ep)
    cache: dict = {}

    def eval_fn(state: TrainState, tokens, targets):
        logits = model.apply({"params": state.params}, tokens, train=False)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        hits = jnp.sum(jnp.argmax(logits, -1) == targets)
        total = lax.psum(jnp.float32(ce.size), data_axes)
        return {
            "loss": lax.psum(ce.sum(), data_axes) / total,
            "accuracy": lax.psum(hits.astype(jnp.float32),
                                 data_axes) / total,
        }

    def runner(state, tokens, targets):
        key = jax.tree.structure(state)
        if key not in cache:
            specs = moe_state_specs(state, axis_ep)
            cache[key] = jax.jit(shard_map(
                eval_fn, mesh=mesh,
                in_specs=(specs, P(data_axes), P(data_axes)),
                out_specs=P(), check_vma=False))
        return cache[key](state, tokens, targets)

    return runner
