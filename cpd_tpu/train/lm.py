"""LM train step over a ("dp","sp","tp") mesh — the long-context /
multi-axis companion of train/step.py.

One jitted shard_map program per config, composing every parallel axis the
framework supports:

* dp — data parallelism with the reference's quantized gradient all-reduce
  (APS / ordered / Kahan, parallel/dist.py) — the low-precision collective
  is the framework's core capability (reference dist_util.py:22-89);
* sp — sequence parallelism: tokens sharded on T, Ring Attention inside
  the model (ops/attention.py), plus an fp32 `psum` of gradients over sp
  (each sp rank sees different tokens);
* tp — Megatron tensor parallelism: params sharded per
  `lm_param_specs`, activations replicated between the per-block psums;
  replicated-param gradients are `psum`'d over tp, sharded-param gradients
  are already complete on their shard.

Gradient flow: local grads → psum over sp (all) → psum over tp
(replicated params only) → the gradient stage's emulated-node reduce and
quantized sum over dp (train/grads.py) → optimizer.
The optimizer update runs shard-local, which is exact for the elementwise
SGD family (train/optim.py); LARS trust ratios would need global norms —
use sgd/nesterov here.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import optax
from flax.traverse_util import flatten_dict
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..models.transformer import lm_param_specs
from ..compat import shard_map
from ..obs import scopes
from .grads import ReduceOptions, reduced, report_metrics
from .state import (TrainState, make_sharded_stepper, reject_norm_based,
                    state_specs_like)

__all__ = ["make_lm_train_step", "make_lm_eval_step", "lm_state_specs"]

# the method of a model that owns its loss: (tokens, targets, train=True)
# -> ((B, T) float32 loss terms, hits); the step takes the mean
OWN_LOSS = "token_losses"


def lm_state_specs(state: TrainState, tp_axis: str = "tp") -> TrainState:
    """PartitionSpec pytree shaped like `state`: params (and their optimizer
    momentum mirror) follow the Megatron rules, scalars replicated."""
    return state_specs_like(state, lm_param_specs(state.params, tp_axis))


def make_lm_train_step(model, tx: optax.GradientTransformation, mesh: Mesh,
                       *, axis_dp: str = "dp", axis_sp: str = "sp",
                       axis_tp: str = "tp", emulate_node: int = 1,
                       use_aps: bool = False, grad_exp: int = 8,
                       grad_man: int = 23, use_kahan: bool = False,
                       mode: str = "faithful", donate: bool = True,
                       label_smoothing: float = 0.0, rng_seed: int = 0,
                       grad_rounding: str = "nearest", grad_seed: int = 0,
                       verify_reduce: bool = False,
                       wire_fault_plan=None,
                       quant_stats: bool = False,
                       sat_fault_plan=None,
                       overlap_reduce: bool = False,
                       bucket_elems=None,
                       block_scale: bool = False,
                       block_size: int = 128):
    """Build jitted ``(state, tokens, targets) -> (state, metrics)``.

    ``metrics`` holds ``loss`` and ``accuracy`` and, for a model that
    declares ``step_counters`` ({name: "sum" | "max" | "mean"}; its layers
    ``sow`` them into the ``"counters"`` collection), each counter merged
    over layers, micro-batches and the dp/sp ranks (tp ranks repeat them).

    tokens/targets: (global_batch * emulate_node, T_global) int32, sharded
    (dp, sp).  Loss is next-token CE averaged over all target positions;
    ``label_smoothing`` in [0, 1) mixes the one-hot targets with uniform
    mass (training loss only — eval stays plain CE).  A model whose loss
    is not the cross-entropy of one logits tensor owns it: it has a
    method ``token_losses(tokens, targets, train=True)`` that returns the
    (B, T) float32 loss of every token and how many of them it predicts
    right (models/looped.py), and is asked for those instead of its
    logits; ``label_smoothing`` with such a model raises.

    use_aps ... block_size, the fifteen keywords of the dp reduction, are
    `train.grads.ReduceOptions`' fields and are described there, once.
    What is this builder's own about them: the sp/tp psums stay
    unverified, unblocked and fp32 (XLA's own collectives, no custom
    wire); the saturation pressure scales the post-sp/tp-psum gradients,
    so every dp rank's wire cast sees it identically; under
    overlap_reduce each leaf's sp psum (and tp psum for replicated
    params) moves INTO its bucket's tap, so the whole per-leaf reduction
    chain starts when that bucket closes.
    """
    if not 0.0 <= label_smoothing < 1.0:
        raise ValueError(f"label_smoothing must be in [0, 1), got "
                         f"{label_smoothing}")
    opts = ReduceOptions(
        use_aps=use_aps, grad_exp=grad_exp, grad_man=grad_man,
        use_kahan=use_kahan, mode=mode, grad_rounding=grad_rounding,
        grad_seed=grad_seed, verify_reduce=verify_reduce,
        wire_fault_plan=wire_fault_plan, quant_stats=quant_stats,
        sat_fault_plan=sat_fault_plan, overlap_reduce=overlap_reduce,
        bucket_elems=bucket_elems, block_scale=block_scale,
        block_size=block_size).check()
    # Guard: the optimizer update runs shard-local, which is only exact for
    # elementwise transforms (see reject_norm_based).  With tp=1 all params
    # are replicated and grads fully reduced before the update, so
    # per-shard norms ARE global norms — LARS is fine there.
    if mesh.shape.get(axis_tp, 1) > 1:
        reject_norm_based(tx, "tp-sharded LM step")

    own_loss = hasattr(model, OWN_LOSS)
    if own_loss and label_smoothing:
        raise ValueError(
            f"label_smoothing {label_smoothing} with a model that owns its "
            f"loss ({type(model).__name__}.{OWN_LOSS}): the smoothing is of "
            f"the step's own cross-entropy, which this model does not use")
    has_dropout = getattr(model, "dropout_rate", 0.0) > 0.0
    # counters a model reports: {name: "sum" | "max" | "mean"}, sown into
    # the "counters" collection by its layers (models/mla_moe.py).  A model
    # that declares none is applied as before, equation for equation
    counters = dict(getattr(model, "step_counters", None) or {})
    merge = {"sum": (jnp.sum, lax.psum), "max": (jnp.max, lax.pmax),
             "mean": (jnp.mean, lax.pmean)}
    for name, how in counters.items():
        if how not in merge:
            raise ValueError(f"step counter {name!r}: unknown merge {how!r}")

    def model_counts(sown) -> dict:
        """One value a counter: what the layers sowed under its name
        (`{layer: {..: {name: (value,)}}}`), merged over the layers."""
        by_name = {name: [] for name in counters}
        for path, values in flatten_dict(sown).items():
            by_name[path[-1]].extend(values)
        return {name: merge[counters[name]][0](jnp.stack(values))
                for name, values in by_name.items()}

    def step_fn(state: TrainState, tokens, targets):
        def loss_of(params, _, xy, micro_idx):
            toks, tgts = xy
            rngs = {}
            if has_dropout:
                # deterministic in (seed, global step, micro index) and
                # decorrelated across dp/sp ranks — but NOT tp: the tp
                # ranks compute the same activations redundantly, so
                # their masks must be identical (Block applies dropout
                # post-psum)
                key = jax.random.fold_in(jax.random.PRNGKey(rng_seed),
                                         state.step * emulate_node
                                         + micro_idx)
                key = jax.random.fold_in(
                    key, lax.axis_index(axis_dp).astype(jnp.int32))
                key = jax.random.fold_in(
                    key, lax.axis_index(axis_sp).astype(jnp.int32))
                rngs = {"dropout": key}
            counts = {}
            if own_loss:
                (ce, hits), sown = model.apply(
                    {"params": params}, toks, tgts, train=True, rngs=rngs,
                    mutable=["counters"], method=OWN_LOSS)
                if counters:
                    counts = model_counts(sown["counters"])
            elif counters:
                logits, sown = model.apply(
                    {"params": params}, toks, train=True, rngs=rngs,
                    mutable=["counters"])
                counts = model_counts(sown["counters"])
            else:
                logits = model.apply({"params": params}, toks, train=True,
                                     rngs=rngs)
            if not own_loss:
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits, tgts)                   # (B_local, T_local)
            if label_smoothing:
                # closed form of CE against one_hot*(1-a) + a/V targets:
                # (1-a)*CE_int + a*(logsumexp - mean(logits)) — no dense
                # (B, T, V) target tensor, which at long-context shapes
                # (V=32k) would cost GBs per microbatch
                lf32 = logits.astype(jnp.float32)
                uniform = (jax.scipy.special.logsumexp(lf32, axis=-1)
                           - lf32.mean(axis=-1))
                ce = ((1.0 - label_smoothing) * ce
                      + label_smoothing * uniform)
            local_sum = ce.sum()
            local_n = jnp.float32(ce.size)
            # Normalizer includes the tp axis: the loss is computed
            # redundantly on every tp rank and shard_map's transpose of the
            # forward tp-psums sums those redundant cotangents, so without
            # the /tp every gradient comes out exactly tp-times too large
            # (verified against single-device grads).
            global_n = lax.psum(local_n, (axis_dp, axis_sp, axis_tp))
            # normalize by the emulated-cluster size too (mix.py:239's
            # divide-so-the-sum-is-the-mean, per micro-batch)
            loss = local_sum / global_n / emulate_node
            if not own_loss:
                hits = jnp.sum(jnp.argmax(logits, -1) == tgts)
            return loss, (None, (local_sum, local_n, hits, counts))

        # --- cross-axis gradient reduction (see module docstring) ---
        specs = jax.tree.leaves(lm_param_specs(state.params, axis_tp),
                                is_leaf=lambda s: isinstance(s, P))

        def sp_tp_reduce(g, i):
            g = lax.psum(g, axis_sp)
            if specs[i] == P():             # replicated param: finish tp sum
                g = lax.psum(g, axis_tp)
            return g

        out = reduced(
            loss_of, state.params, (tokens, targets), n=emulate_node,
            carry=None, step=state.step, axis_dp=axis_dp, opts=opts,
            leaf_pre=sp_tp_reduce)
        sums, ns, hits, counts = out.aux

        with jax.named_scope(scopes.OPTIMIZER):
            updates, new_opt = tx.update(out.grads, state.opt_state,
                                         state.params)
            new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(step=state.step + 1, params=new_params,
                               batch_stats=state.batch_stats,
                               opt_state=new_opt)
        # metrics use the dp/sp token count only (tp ranks duplicate the
        # same tokens, and these psums exclude tp)
        from ..resilience.guard import guard_metrics
        with jax.named_scope(scopes.METRICS):
            total_n = lax.psum(ns.sum(), (axis_dp, axis_sp))
            metrics = {
                **guard_metrics(new_opt),
                "loss": lax.psum(sums.sum(), (axis_dp, axis_sp)) / total_n,
                "accuracy": lax.psum(hits.sum().astype(jnp.float32),
                                     (axis_dp, axis_sp)) / total_n,
            }
            for name, how in counters.items():
                over_micros, over_ranks = merge[how]
                metrics[name] = over_ranks(over_micros(counts[name]),
                                           (axis_dp, axis_sp))
        metrics.update(report_metrics(out.report, opts))
        return new_state, metrics

    return make_sharded_stepper(
        step_fn, lambda s: lm_state_specs(s, axis_tp), mesh,
        P(axis_dp, axis_sp), donate=donate)


def make_lm_eval_step(model, mesh: Mesh, *, axis_dp: str = "dp",
                      axis_sp: str = "sp", axis_tp: str = "tp"):
    """Jitted ``(state, tokens, targets) -> {'loss','accuracy'}`` over the
    same dp x sp x tp sharding as the train step (no grads, no update)."""
    cache: dict = {}

    def eval_fn(state: TrainState, tokens, targets):
        logits = model.apply({"params": state.params}, tokens, train=False)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        hits = jnp.sum(jnp.argmax(logits, -1) == targets)
        total_n = lax.psum(jnp.float32(ce.size), (axis_dp, axis_sp))
        return {
            "loss": lax.psum(ce.sum(), (axis_dp, axis_sp)) / total_n,
            "accuracy": lax.psum(hits.astype(jnp.float32),
                                 (axis_dp, axis_sp)) / total_n,
        }

    def runner(state, tokens, targets):
        key = jax.tree.structure(state)
        if key not in cache:
            specs = lm_state_specs(state, axis_tp)
            data_spec = P(axis_dp, axis_sp)
            cache[key] = jax.jit(shard_map(
                eval_fn, mesh=mesh,
                in_specs=(specs, data_spec, data_spec),
                out_specs=P(), check_vma=False))
        return cache[key](state, tokens, targets)

    return runner


def ir_programs(reg):
    """Program-contract declarations (analysis/ir/registry.py): the LM
    step builder on the dp x sp x tp mesh, overlap on/off — the twins
    whose bitwise parity tests/test_overlap.py gates.  `ir-schedule`
    pins their collective multisets identical (the dp ring wire AND the
    forward sp ring-attention ppermutes), `ir-overlap` the interleaving
    verdicts, `ir-bitwise` the absence of ulp-unstable transcendentals
    under the whole traced step (constant LR for the same reason as the
    vision declarations — `pow` is not the contract)."""
    from ..models.transformer import transformer_lm
    from .optim import make_optimizer
    from .state import create_train_state

    deps = ("cpd_tpu.train.lm", "cpd_tpu.train.grads",
            "cpd_tpu.parallel.dist",
            "cpd_tpu.parallel.ring", "cpd_tpu.parallel.overlap",
            "cpd_tpu.parallel.aps", "cpd_tpu.quant.numerics",
            "cpd_tpu.models.transformer")

    def _lm(overlap):
        def build():
            from ..parallel.mesh import make_mesh
            mesh = make_mesh(dp=2, sp=2, tp=2)
            model = transformer_lm(vocab_size=64, d_model=32,
                                   n_layers=2, n_heads=4, tp_axis="tp",
                                   sp_axis="sp", tp_size=2)
            init_model = transformer_lm(vocab_size=64, d_model=32,
                                        n_layers=2, n_heads=4)
            tx = make_optimizer("sgd", lambda step: 0.01, momentum=0.9)
            state = jax.eval_shape(lambda: create_train_state(
                init_model, tx, jnp.zeros((1, 16), jnp.int32),
                jax.random.PRNGKey(0)))
            step = make_lm_train_step(
                model, tx, mesh, mode="ring", use_aps=True, grad_exp=5,
                grad_man=2, grad_rounding="stochastic", grad_seed=3,
                donate=False, bucket_elems=2000,
                overlap_reduce=overlap)
            toks = jax.ShapeDtypeStruct((4, 16), jnp.int32)
            return step, (state, toks, toks)
        return build

    # the monolith carries NO overlap expectation: the forward pass's
    # sp ring-attention ppermutes legitimately precede all backward
    # compute, so the structural probe reads "interleaved" on both
    # twins — only the overlapped step's verdict is a contract here
    reg.declare("lm.ring[e5m2,sr,aps]", _lm(False),
                deps=deps, axis_sizes={"dp": 2, "sp": 2, "tp": 2},
                bitwise=True, twin="lm.ring-overlap")
    reg.declare("lm.ring[e5m2,sr,aps]+overlap", _lm(True),
                deps=deps, axis_sizes={"dp": 2, "sp": 2, "tp": 2},
                bitwise=True, twin="lm.ring-overlap", overlap=True)
