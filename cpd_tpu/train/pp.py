"""Pipeline-parallel LM train step over a ("dp", "pp") mesh.

Companion of train/lm.py for the `pp` axis (round-1 review: pp was a
placeholder).  One jitted shard_map program:

* dp — data parallelism with the reference's quantized gradient all-reduce
  (APS / ordered / Kahan, parallel/dist.py);
* pp — GPipe pipelining (parallel/pipeline.py): tokens replicated over pp,
  microbatches streamed through layer stages, loss computed on the last
  stage and masked to zero elsewhere.

Gradient flow: block (stage-local) grads are complete per pp rank — each
rank is the sole owner of its layer slice; replicated params (embed, ln_f)
get a `psum` over pp (embedding gradients arrive on stage 0 via the input
path and on the last stage via the tied head).  Then the dp quantized
`sum_gradients`, then a shard-local elementwise optimizer update (the same
exactness argument as train/lm.py — LARS refused).

With ``model.vocab_pp`` (round 5) the tied table is vocab-sharded over pp
(models/pipeline_lm.py docstring): its grads are shard-complete (no pp
psum — the spec-driven `reduce_leaf` already skips sharded leaves) and
the loss runs through `vocab_parallel_ce` on the (B, T, V/pp) logits
slices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..models.pipeline_lm import (PipelinedLM, pp_param_specs,
                                  vocab_parallel_ce)
from ..compat import shard_map
from .grads import ReduceOptions, reduce_local
from .state import (TrainState, make_sharded_stepper, reject_norm_based,
                    state_specs_like)

__all__ = ["make_pp_train_step", "make_pp_eval_step", "pp_state_specs"]


def pp_state_specs(state: TrainState, pp_axis: str = "pp",
                   tp_axis: str = "tp",
                   vocab_pp: bool = False) -> TrainState:
    return state_specs_like(
        state, pp_param_specs(state.params, pp_axis, tp_axis, vocab_pp))


def make_pp_train_step(model: PipelinedLM, tx: optax.GradientTransformation,
                       mesh: Mesh, *, n_microbatches: int = 4,
                       axis_dp: str = "dp", axis_pp: str = "pp",
                       axis_tp: str = "tp", use_aps: bool = False,
                       grad_exp: int = 8, grad_man: int = 23,
                       use_kahan: bool = False, mode: str = "faithful",
                       grad_rounding: str = "nearest", grad_seed: int = 0,
                       donate: bool = True):
    """Build jitted ``(state, tokens, targets) -> (state, metrics)``.

    tokens/targets: (global_batch, T) int32 sharded over dp (replicated
    over pp); the per-dp-rank batch is split into `n_microbatches`
    pipeline microbatches.  Keep n_microbatches >= pp for a small bubble
    (fraction (pp-1)/(n_microbatches+pp-1)).

    use_aps ... grad_seed are the seven of `train.grads.ReduceOptions`'
    fields this builder takes (described there; the rest keep their
    defaults).  Its own about grad_rounding='stochastic': the stage's key
    depends only on (grad_seed, step), so it is identical across pp/tp
    ranks, which is required (replicated leaves like the embedding must
    reduce to identical bits on every pp copy) and harmless for
    stage-sharded leaves (pp ranks hold different parameters, nothing
    sums across pp).
    """
    opts = ReduceOptions(use_aps=use_aps, grad_exp=grad_exp,
                         grad_man=grad_man, use_kahan=use_kahan, mode=mode,
                         grad_rounding=grad_rounding,
                         grad_seed=grad_seed).check()
    reject_norm_based(tx, "pp-sharded step")
    pp_size = mesh.shape.get(axis_pp, 1)
    all_axes = (axis_dp, axis_pp, axis_tp)  # size-1 axes psum as no-ops

    def step_fn(state: TrainState, tokens, targets):
        is_last = (lax.axis_index(axis_pp) == pp_size - 1
                   ).astype(jnp.float32)

        def loss_of(params, toks, tgts):
            logits = model.apply_pipelined({"params": params}, toks,
                                           n_microbatches)
            if model.vocab_pp:
                # vocab-sharded logits (B, T, V/pp), valid on EVERY pp
                # rank (the head broadcast already ran inside
                # apply_pipelined); the CE is a pp collective.  is_last
                # masking still applies — it de-duplicates the count and
                # routes exactly one rank's cotangent into the psum
                # transposes (which re-broadcast it to every slice).
                ce, pred = vocab_parallel_ce(logits, tgts, axis_pp)
            else:
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits, tgts)
                pred = jnp.argmax(logits, -1)
            # valid on the last stage only; masking zeroes both the loss
            # and (through autodiff) every non-last-stage head cotangent
            local_sum = ce.sum() * is_last
            local_n = jnp.float32(ce.size) * is_last
            # tp ranks compute the loss redundantly; /tp via the global
            # count (same correction as train/lm.py:101-108)
            global_n = lax.psum(local_n, all_axes)
            hits = jnp.sum(pred == tgts) * is_last
            return local_sum / global_n, (local_sum, local_n, hits)

        (_, (lsum, ln, hits)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(state.params, tokens, targets)

        # Replicated params (embed, ln_f): finish the pp/tp sum.  A leaf
        # whose spec names an axis is SHARDED over it (sole owner per
        # shard, grads already complete); a leaf whose spec doesn't is
        # replicated over it and its per-rank grads are partial sums.
        specs = pp_param_specs(state.params, axis_pp, axis_tp,
                               model.vocab_pp)

        def named_axes(spec):
            out = []
            for part in spec:
                if isinstance(part, (tuple, list)):
                    out.extend(part)
                elif part is not None:
                    out.append(part)
            return out

        def reduce_leaf(g, spec):
            axes = tuple(a for a in (axis_pp, axis_tp)
                         if a not in named_axes(spec))
            return lax.psum(g, axes) if axes else g

        grads = jax.tree.map(reduce_leaf, grads, specs,
                             is_leaf=lambda x: isinstance(x, P))
        grads, _ = reduce_local(grads, step=state.step, axis_dp=axis_dp,
                                opts=opts)

        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(step=state.step + 1, params=new_params,
                               batch_stats=state.batch_stats,
                               opt_state=new_opt)
        from ..resilience.guard import guard_metrics
        total = lax.psum(ln, all_axes)
        metrics = {
            **guard_metrics(new_opt),
            "loss": lax.psum(lsum, all_axes) / total,
            "accuracy": lax.psum(hits.astype(jnp.float32), all_axes) / total,
        }
        return new_state, metrics

    return make_sharded_stepper(
        step_fn,
        lambda s: pp_state_specs(s, axis_pp, axis_tp, model.vocab_pp),
        mesh, P(axis_dp), donate=donate)


def make_pp_eval_step(model: PipelinedLM, mesh: Mesh, *,
                      n_microbatches: int = 4, axis_dp: str = "dp",
                      axis_pp: str = "pp", axis_tp: str = "tp"):
    """Jitted ``(state, tokens, targets) -> {'loss','accuracy'}`` over the
    same dp x pp sharding as the train step (no grads, no update)."""
    pp_size = mesh.shape.get(axis_pp, 1)
    all_axes = (axis_dp, axis_pp, axis_tp)
    cache: dict = {}

    def eval_fn(state: TrainState, tokens, targets):
        is_last = (lax.axis_index(axis_pp) == pp_size - 1
                   ).astype(jnp.float32)
        logits = model.apply_pipelined({"params": state.params}, tokens,
                                       n_microbatches)
        if model.vocab_pp:
            ce, pred = vocab_parallel_ce(logits, targets, axis_pp)
        else:
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, targets)
            pred = jnp.argmax(logits, -1)
        hits = jnp.sum(pred == targets) * is_last
        n = jnp.float32(ce.size) * is_last
        total = lax.psum(n, all_axes)
        return {
            "loss": lax.psum(ce.sum() * is_last, all_axes) / total,
            "accuracy": lax.psum(hits.astype(jnp.float32),
                                 all_axes) / total,
        }

    def runner(state, tokens, targets):
        key = jax.tree.structure(state)
        if key not in cache:
            specs = pp_state_specs(state, axis_pp, axis_tp,
                                    model.vocab_pp)
            cache[key] = jax.jit(shard_map(
                eval_fn, mesh=mesh,
                in_specs=(specs, P(axis_dp), P(axis_dp)),
                out_specs=P(), check_vma=False))
        return cache[key](state, tokens, targets)

    return runner
