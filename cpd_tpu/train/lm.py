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
(replicated params only) → quantized sum_gradients over dp → optimizer.
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
from ..parallel.dist import grad_sr_key, sum_gradients
from ..parallel.emulate import emulate_node_reduce
from .state import (TrainState, make_sharded_stepper, reject_norm_based,
                    state_specs_like)

__all__ = ["make_lm_train_step", "make_lm_eval_step", "lm_state_specs"]


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
    declares ``step_counters`` ({name: "sum" | "max"}; its layers ``sow``
    them into the ``"counters"`` collection), each counter merged over
    layers, micro-batches and the dp/sp ranks (tp ranks repeat them).

    tokens/targets: (global_batch * emulate_node, T_global) int32, sharded
    (dp, sp).  Loss is next-token CE averaged over all target positions;
    ``label_smoothing`` in [0, 1) mixes the one-hot targets with uniform
    mass (training loss only — eval stays plain CE).

    verify_reduce / wire_fault_plan: the self-verifying dp reduction and
    its deterministic wire-fault table, exactly as on
    `train.step.make_train_step` (the reduce_ok/... metrics feed the
    transport supervisor).  The sp/tp psums stay unverified — they are
    XLA's own collectives with no custom wire.

    quant_stats / sat_fault_plan: reduce-wire numeric-health telemetry
    (``prec_wire_*`` / ``prec_aps_bad`` metrics feeding the
    `resilience.precision.PrecisionSupervisor`) and the deterministic
    2^k saturation-pressure table, exactly as on `make_train_step` —
    the pressure scales the post-sp/tp-psum local gradients, so every
    dp rank's wire cast sees it identically.

    overlap_reduce / bucket_elems: the bucketed, dependency-scheduled
    transport, exactly as on `make_train_step` (parallel/overlap.py) —
    per-bucket taps run the dp reduction inside the backward; each
    leaf's sp psum (and tp psum for replicated params) moves INTO its
    bucket's tap, so the whole per-leaf reduction chain starts when
    that bucket closes.  Bitwise identical to the monolithic step.
    Composes with emulate_node > 1 (ISSUE 12): the first N-1
    micro-batches run unrolled and their sp/tp-reduced stacked grads
    ride into the last micro-batch's taps, whose per-bucket
    emulate-node reduce + dp collective fire as each bucket closes.

    block_scale / block_size: the EQuARX-style block-scaled ring wire
    for the dp reduction, exactly as on `make_train_step` — ring mode
    only; a distinct accumulation numerics (own StepTable key via
    `ladder_step_key(block=...)`); composes with overlap_reduce
    bitwise.  The sp/tp psums are untouched (fp32 XLA collectives).
    """
    if not 0.0 <= label_smoothing < 1.0:
        raise ValueError(f"label_smoothing must be in [0, 1), got "
                         f"{label_smoothing}")
    if grad_rounding not in ("nearest", "stochastic"):
        raise ValueError(f"unknown grad_rounding {grad_rounding!r}")
    if block_scale and mode != "ring":
        raise ValueError(
            f"block_scale=True needs mode='ring' (got {mode!r}): the "
            f"per-block scale sidecar rides the ring's packed wire")
    # Guard: the optimizer update runs shard-local, which is only exact for
    # elementwise transforms (see reject_norm_based).  With tp=1 all params
    # are replicated and grads fully reduced before the update, so
    # per-shard norms ARE global norms — LARS is fine there.
    if mesh.shape.get(axis_tp, 1) > 1:
        reject_norm_based(tx, "tp-sharded LM step")

    has_dropout = getattr(model, "dropout_rate", 0.0) > 0.0
    # counters a model reports: {name: "sum" | "max"}, sown into the
    # "counters" collection by its layers (models/mla_moe.py).  A model
    # that declares none is applied as before, equation for equation
    counters = dict(getattr(model, "step_counters", None) or {})
    merge = {"sum": (jnp.sum, lax.psum), "max": (jnp.max, lax.pmax)}
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
        def loss_of(params, toks, tgts, micro_idx):
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
            if counters:
                logits, sown = model.apply(
                    {"params": params}, toks, train=True, rngs=rngs,
                    mutable=["counters"])
                counts = model_counts(sown["counters"])
            else:
                logits = model.apply({"params": params}, toks, train=True,
                                     rngs=rngs)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, tgts)                       # (B_local, T_local)
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
            hits = jnp.sum(jnp.argmax(logits, -1) == tgts)
            return loss, (local_sum, local_n, hits, counts)

        n = emulate_node
        mb = tokens.shape[0] // n
        # --- cross-axis gradient reduction (see module docstring) ---
        specs = lm_param_specs(state.params, axis_tp)

        def sp_tp_reduce(stacked_g, spec):
            g = lax.psum(stacked_g, axis_sp)
            if spec == P():                 # replicated param: finish tp sum
                g = lax.psum(g, axis_tp)
            return g

        # SR keys (grad_rounding='stochastic'): the rank-local emulate key
        # folds ONLY the dp index — post-psum grads are identical across
        # sp (and across tp for replicated params), so sp/tp copies must
        # draw identical bits or their optimizer states would diverge;
        # dp ranks hold different grads and decorrelate (see
        # parallel/dist.py on coherent rounding error).
        sr = grad_rounding == "stochastic"
        sum_key = grad_sr_key(grad_seed, state.step, 1) if sr else None
        wf = None
        if wire_fault_plan is not None and mode == "ring":
            codes = jnp.asarray(wire_fault_plan[0], jnp.int32)
            ranks = jnp.asarray(wire_fault_plan[1], jnp.int32)
            idx = jnp.clip(state.step, 0, codes.shape[0] - 1)
            wf = (jnp.where(state.step < codes.shape[0], codes[idx], 0),
                  ranks[idx])
        sfac = None
        if sat_fault_plan is not None:
            # saturation-pressure attack (resilience/inject.py
            # `sat_pressure`): 2^k exact power-of-two scaling, shared
            # lookup (see make_train_step)
            from ..resilience.inject import sat_pressure_factor
            sfac = sat_pressure_factor(sat_fault_plan, state.step)
        vreport = None
        if overlap_reduce:
            # Bucketed dependency-scheduled transport (parallel/
            # overlap.py): per-bucket taps own the WHOLE per-leaf
            # reduction chain — sp psum, tp psum for replicated params
            # (leaf_pre), sat pressure, emulate-node reduce (n > 1:
            # micro-batches 0..N-2 run unrolled and their sp/tp-reduced
            # stacked grads ride into the LAST micro-batch's taps as
            # extras, ISSUE 12 leg 3), then the dp quantized collective
            # — so a bucket's work starts the moment its last cotangent
            # closes.  Bitwise identical to the monolithic path below.
            from ..parallel.overlap import BucketPlan, overlapped_grads
            plan = BucketPlan.for_tree(state.params, bucket_elems)
            specs_flat = jax.tree_util.tree_flatten(
                specs, is_leaf=lambda s: isinstance(s, P))[0]

            def leaf_pre(g, i):
                return sp_tp_reduce(g, specs_flat[i])

            extras = emulate_fn = emu_key = None
            micro_sums, micro_ns, micro_hits, micro_counts = [], [], [], []
            if n > 1:
                toks_u = tokens.reshape(n, mb, tokens.shape[1])
                tgts_u = targets.reshape(n, mb, targets.shape[1])
                prev = []
                for mi in range(n - 1):
                    with jax.named_scope(scopes.LOSS_GRAD):
                        (_, (s_mi, n_mi, h_mi, c_mi)), g_mi = (
                            jax.value_and_grad(loss_of, has_aux=True)(
                                state.params, toks_u[mi], tgts_u[mi],
                                jnp.int32(mi)))
                    micro_sums.append(s_mi)
                    micro_ns.append(n_mi)
                    micro_hits.append(h_mi)
                    micro_counts.append(c_mi)
                    prev.append(jax.tree_util.tree_leaves(g_mi))
                # sp/tp-reduce + sat-scale the prior micros here (the
                # taps apply leaf_pre/aux[0] to the LAST micro's
                # cotangent only) — elementwise psums, so per-micro
                # equals the monolith's stacked psum bit for bit
                extras = []
                for i in range(len(plan.sizes)):
                    st = jnp.stack([prev[mi][i] for mi in range(n - 1)])
                    st = sp_tp_reduce(st, specs_flat[i])
                    if sfac is not None:
                        st = st * sfac
                    extras.append(st)
                if sr:
                    emu_key = jax.random.fold_in(
                        grad_sr_key(grad_seed, state.step, 0),
                        lax.axis_index(axis_dp).astype(jnp.int32))
                from ..parallel.emulate import make_overlap_emulate_fn
                emulate_fn = make_overlap_emulate_fn(
                    n, use_aps, grad_exp, grad_man, sr)
                tk_last, tg_last = toks_u[n - 1], tgts_u[n - 1]
                last_idx = jnp.int32(n - 1)
            else:
                tk_last, tg_last = tokens, targets
                last_idx = jnp.zeros([], jnp.int32)

            def loss_closure(p):
                loss, aux = loss_of(p, tk_last, tg_last, last_idx)
                return loss, aux

            ((_, (l_sum, l_n, l_hits, l_counts)), reduced,
             vreport) = overlapped_grads(
                loss_closure, state.params, axis_name=axis_dp, plan=plan,
                reduce_kw=dict(use_aps=use_aps, grad_exp=grad_exp,
                               grad_man=grad_man, use_kahan=use_kahan,
                               mode=mode, rounding=grad_rounding,
                               bucket_elems=bucket_elems,
                               block_scale=block_scale,
                               block_size=block_size),
                key=sum_key, sat_factor=sfac, wire_fault=wf,
                verify=verify_reduce, stats=quant_stats,
                leaf_pre=leaf_pre, collective=None, extras=extras,
                emulate_reduce=emulate_fn, emulate_key=emu_key)
            sums = jnp.stack(micro_sums + [l_sum])
            ns = jnp.stack(micro_ns + [l_n])
            hits = jnp.stack(micro_hits + [l_hits])
            counts = {name: jnp.stack([c[name] for c in
                                       micro_counts + [l_counts]])
                      for name in counters}
        else:
            toks = tokens.reshape(n, mb, tokens.shape[1])
            tgts = targets.reshape(n, mb, targets.shape[1])

            def micro(micro_idx, xy):
                tk, tg = xy
                (_, aux), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(state.params, tk, tg, micro_idx)
                return micro_idx + 1, (grads, *aux)

            with jax.named_scope(scopes.LOSS_GRAD):
                _, (stacked, sums, ns, hits, counts) = lax.scan(
                    micro, jnp.zeros([], jnp.int32), (toks, tgts))

            stacked = jax.tree.map(sp_tp_reduce, stacked, specs)
            if sfac is not None:
                stacked = jax.tree.map(lambda g: g * sfac, stacked)
            local = emulate_node_reduce(
                stacked, n, use_aps, grad_exp, grad_man,
                rounding=grad_rounding,
                key=jax.random.fold_in(
                    grad_sr_key(grad_seed, state.step, 0),
                    lax.axis_index(axis_dp).astype(jnp.int32)) if sr
                else None)
            reduced = sum_gradients(
                local, axis_dp, use_aps=use_aps,
                grad_exp=grad_exp, grad_man=grad_man,
                use_kahan=use_kahan, mode=mode, rounding=grad_rounding,
                key=sum_key, verify=verify_reduce, wire_fault=wf,
                stats=quant_stats, bucket_elems=bucket_elems,
                block_scale=block_scale, block_size=block_size)
            if verify_reduce or quant_stats:
                reduced, vreport = reduced

        with jax.named_scope(scopes.OPTIMIZER):
            updates, new_opt = tx.update(reduced, state.opt_state,
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
        if vreport is not None:
            f32 = jnp.float32
            if verify_reduce:
                metrics.update(
                    reduce_ok=vreport["ok"].astype(f32),
                    reduce_hop_bad=vreport["hop_bad"].astype(f32),
                    reduce_gather_bad=vreport["gather_bad"].astype(f32),
                    reduce_agree=vreport["agree"].astype(f32))
            if quant_stats:
                metrics.update(
                    prec_wire_sat=vreport["wire_sat"].astype(f32),
                    prec_wire_underflow=vreport["wire_underflow"]
                    .astype(f32),
                    prec_wire_nan=vreport["wire_nan"].astype(f32),
                    prec_wire_total=vreport["wire_total"].astype(f32),
                    prec_aps_bad=vreport["aps_bad"].astype(f32))
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

    deps = ("cpd_tpu.train.lm", "cpd_tpu.parallel.dist",
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
