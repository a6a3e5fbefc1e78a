"""The gradient stage: from a micro-batch's loss to the gradient the
optimizer takes, written once for every step builder.

`make_train_step` and `make_lm_train_step` call `reduced` (micro-batch
schedule, emulated-node reduction, dp collective); `make_moe_train_step`
and `make_pp_train_step`, which differentiate once, call `reduce_local`
(the collective alone).  A builder keeps its loss, its carry, its rngs,
its sum over the model axes and its optimizer; what lies between is here,
and a change to it is made and gated bitwise once.

The order, which the overlapped taps mirror (parallel/overlap.py): the
builder's per-leaf sum over its model axes (`leaf_pre`), the 2^k
saturation pressure on the summed gradients, the rank-local emulated-node
reduction (mix.py:251-282), the quantized collective over dp
(mix.py:286-291).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import scopes
from ..parallel.dist import grad_sr_key, sum_gradients
from ..parallel.emulate import emulate_node_reduce, make_overlap_emulate_fn
from ..parallel.overlap import (BucketPlan, extract_bucket_shards,
                                overlapped_grads)

__all__ = ["ReduceOptions", "Reduced", "reduced", "reduce_local",
           "report_metrics"]


@dataclasses.dataclass(frozen=True)
class ReduceOptions:
    """What a builder's keywords say of the reduction, gathered once.

    use_aps / grad_exp / grad_man / use_kahan / mode: the wire format and
    transport of `parallel.dist.sum_gradients`.

    grad_rounding="stochastic" / grad_seed: fresh unbiased rounding bits
    every step from `grad_sr_key` (rank-free by contract, so replicated
    outputs stay consistent).  Site 0 keys the rank-LOCAL emulated-node
    reduce and folds in the dp rank only: gradients summed over the model
    axes are identical on those axes' copies, which must draw identical
    bits or their optimizer states diverge.  Site 1 keys the collective,
    which folds the dp rank into its own pre-quantize key.

    verify_reduce: the self-verifying reduction (parallel/integrity.py);
    `report_metrics` adds ``reduce_ok`` / ``reduce_hop_bad`` /
    ``reduce_gather_bad`` / ``reduce_agree``, the feed of
    `resilience.transport.TransportSupervisor`.  wire_fault_plan is a
    ``FaultPlan.wire_schedule(n_steps)`` (codes, ranks) table baked into
    the program; entry ``step`` corrupts the ring wire on that rank
    (ignored outside mode="ring": the ring's wire IS the one under
    attack, and downgrading transports is the escape).

    quant_stats: the reduce wire's numeric-health telemetry as
    ``prec_wire_sat`` / ``prec_wire_underflow`` / ``prec_wire_nan`` /
    ``prec_wire_total`` / ``prec_aps_bad``, the feed of
    `resilience.precision.PrecisionSupervisor`; the gradient path stays
    bitwise unchanged.  sat_fault_plan is a
    ``FaultPlan.sat_schedule(n_steps)`` int32 exponent table: entry
    ``step`` scales the local gradients by 2^k before the emulated-node
    reduce and the collective, driving the wire cast into saturation
    (0 = off, and scaling by 2^0 is an exact fp32 no-op).

    overlap_reduce: the bucketed, dependency-scheduled transport
    (parallel/overlap.py; MLPerf TPU-pod bucketed gradient summation,
    PAPERS.md #4).  Per-bucket custom_vjp taps on the parameters run the
    whole per-leaf chain above INSIDE the backward pass the moment a
    bucket's last cotangent closes, so late-layer buckets ring while
    early-layer backward compute is pending.  With more than one
    micro-batch the first N-1 run unrolled (the scan's sequential carry
    order) and their stacked gradients ride into the LAST one's taps
    (ISSUE 12).  Gradients, hence parameters, are BITWISE those of the
    monolith (tests/test_overlap.py); a carry of batch statistics agrees
    to the last ulp only, since XLA fuses the scanned and the unrolled
    forward differently.  Reports ride out of the backward on the
    tap-cotangent channel; wire faults hit bucket 0 only, which keeps the
    drills' counters exact.  bucket_elems caps the bucket size of the
    taps AND of the post-backward bucketed and ring layouts (default:
    parallel/dist._BUCKET_ELEMS).

    block_scale / block_size: the EQuARX-style block-scaled ring wire
    (quant/numerics.py "Block-scaled eXmY codec"): every hop cast shares
    one power-of-2 scale per `block_size` elements, the shifts ride the
    packed wire.  A DIFFERENT documented accumulation numerics than
    per-tensor, with its own StepTable key (`ladder_step_key(block=...)`);
    overlap on/off stays bitwise identical with it on.  Ring mode only,
    except where an updater owns the collective and ZeRO-2's faithful
    all_to_all carries the blocked wire instead (parallel/zero.py).
    """
    use_aps: bool = False
    grad_exp: int = 8
    grad_man: int = 23
    use_kahan: bool = False
    mode: str = "faithful"
    grad_rounding: str = "nearest"
    grad_seed: int = 0
    verify_reduce: bool = False
    wire_fault_plan: Optional[tuple] = None
    quant_stats: bool = False
    sat_fault_plan: Optional[Any] = None
    overlap_reduce: bool = False
    bucket_elems: Optional[int] = None
    block_scale: bool = False
    block_size: int = 128

    def check(self, reduce: bool = True) -> "ReduceOptions":
        """Refuse at build time what is wrong whatever the model;
        `reduce` as `reduced` takes it."""
        if self.grad_rounding not in ("nearest", "stochastic"):
            raise ValueError(f"unknown grad_rounding {self.grad_rounding!r}")
        if self.block_scale and self.mode != "ring" and reduce:
            raise ValueError(
                f"block_scale=True needs mode='ring' (got {self.mode!r}): "
                f"the per-block scale sidecar rides the ring's packed wire "
                f"(an updater that owns the collective, ZeRO-2, carries it "
                f"on its all_to_all instead: parallel/zero.py)")
        return self

    @property
    def stochastic(self) -> bool:
        return self.grad_rounding == "stochastic"

    def wire_kw(self) -> dict:
        """The wire as a collective's keywords name it: all that an
        updater's own collective or tap plan is told."""
        return dict(use_aps=self.use_aps, grad_exp=self.grad_exp,
                    grad_man=self.grad_man, use_kahan=self.use_kahan,
                    mode=self.mode, rounding=self.grad_rounding,
                    block_scale=self.block_scale,
                    block_size=self.block_size)

    def reduce_kw(self) -> dict:
        """`overlapped_grads`' ``reduce_kw``; with the per-step operands,
        `sum_gradients`' keywords."""
        return dict(self.wire_kw(), bucket_elems=self.bucket_elems)


class Reduced(NamedTuple):
    """What `reduced` hands back.  `grads` is what the optimizer takes;
    where an updater owns the collective (``reduce=False``) it is what
    that updater takes, and `update_kw` tells it what is left to do."""
    grads: Any
    carry: Any
    aux: Any            # the builder's own pytree, stacked over micro-batches
    report: Optional[dict]
    update_kw: dict


def _step_operands(step, opts: ReduceOptions):
    """The per-step operands, looked up by the optimizer-update index
    (the clock of `with_fault_injection`'s grad schedule): the
    collective's rounding key, the wire fault, the saturation factor."""
    sum_key = grad_sr_key(opts.grad_seed, step, 1) if opts.stochastic \
        else None
    wire_fault = None
    if opts.wire_fault_plan is not None and opts.mode == "ring":
        codes = jnp.asarray(opts.wire_fault_plan[0], jnp.int32)
        ranks = jnp.asarray(opts.wire_fault_plan[1], jnp.int32)
        idx = jnp.clip(step, 0, codes.shape[0] - 1)
        in_range = step < codes.shape[0]
        wire_fault = (jnp.where(in_range, codes[idx], 0), ranks[idx])
    sat = None
    if opts.sat_fault_plan is not None:
        # resilience/inject.py `sat_pressure`: an exact power of two,
        # the same on every replica, so replication is preserved
        from ..resilience.inject import sat_pressure_factor
        sat = sat_pressure_factor(opts.sat_fault_plan, step)
    return sum_key, wire_fault, sat


def _emulate_key(step, axis_dp, opts: ReduceOptions):
    if not opts.stochastic:
        return None
    return jax.random.fold_in(grad_sr_key(opts.grad_seed, step, 0),
                              lax.axis_index(axis_dp).astype(jnp.int32))


def _collective(local, axis_dp, opts: ReduceOptions, sum_key, wire_fault):
    out = sum_gradients(local, axis_dp, key=sum_key,
                        verify=opts.verify_reduce, wire_fault=wire_fault,
                        stats=opts.quant_stats, **opts.reduce_kw())
    if opts.verify_reduce or opts.quant_stats:
        return out
    return out, None


def reduce_local(local, *, step, axis_dp: str, opts: ReduceOptions):
    """The stage's tail for a builder that differentiates once: a
    rank-local gradient, already summed over the model axes, to
    ``(reduced, report)``."""
    sum_key, wire_fault, sat = _step_operands(step, opts)
    if sat is not None:
        local = jax.tree.map(lambda g: g * sat, local)
    return _collective(local, axis_dp, opts, sum_key, wire_fault)


def reduced(loss_of: Callable, params, batch, *, n: int, carry, step,
            axis_dp: str, opts: ReduceOptions,
            leaf_pre: Optional[Callable] = None,
            tap_reduce: Optional[Callable] = None,
            reduce: bool = True) -> Reduced:
    """Run the micro-batches and reduce their gradients.

    ``loss_of(params, carry, x, micro_idx) -> (loss, (carry, aux))`` is
    differentiated in `params`, once a micro-batch: the rank's `batch`
    (a pytree, batch axis leading) is split into `n` of them, the
    emulated nodes, and `carry` goes from one to the next in order
    (batch statistics; None where there is nothing to carry).
    ``leaf_pre(g, leaf_index)`` sums a leaf, single or stacked, over the
    builder's model axes.  ``reduce=False`` leaves the collective to the
    updater: it gets the local gradient with the wire's keywords, or,
    with overlap, the shards its ``tap_reduce(params, axis_dp, wire_kw)
    -> (plan, chunks, collective)`` hook reduce-scattered inside the
    backward pass (ZeRO-2, parallel/zero.py).
    """
    sum_key, wire_fault, sat = _step_operands(step, opts)
    xs = jax.tree.map(
        lambda a: a.reshape(n, a.shape[0] // n, *a.shape[1:]), batch)
    grad_of = jax.value_and_grad(loss_of, has_aux=True)

    if not opts.overlap_reduce:
        def micro(c, x):
            (_, (c_next, aux)), g = grad_of(params, c[0], x, c[1])
            return (c_next, c[1] + 1), (g, aux)

        with jax.named_scope(scopes.LOSS_GRAD):
            (carry, _), (stacked, aux) = lax.scan(
                micro, (carry, jnp.zeros([], jnp.int32)), xs)
        if leaf_pre is not None:
            leaves, treedef = jax.tree.flatten(stacked)
            stacked = treedef.unflatten(
                [leaf_pre(g, i) for i, g in enumerate(leaves)])
        if sat is not None:
            stacked = jax.tree.map(lambda g: g * sat, stacked)
        local = emulate_node_reduce(
            stacked, n, opts.use_aps, opts.grad_exp, opts.grad_man,
            rounding=opts.grad_rounding,
            key=_emulate_key(step, axis_dp, opts))
        if not reduce:
            # the key the replicated path hands `sum_gradients`, so a
            # reduce-scatter draws the bits that reduction would
            return Reduced(local, carry, aux, None,
                           dict(opts.wire_kw(), key=sum_key))
        grads, report = _collective(local, axis_dp, opts, sum_key,
                                    wire_fault)
        return Reduced(grads, carry, aux, report, {})

    if tap_reduce is not None:
        plan, chunks, collective = tap_reduce(params, axis_dp,
                                              opts.wire_kw())
        if (opts.bucket_elems is not None
                and plan.bucket_elems != opts.bucket_elems):
            # the update must consume the shards the taps produce, so
            # the plan is the updater's; a cap here that differs would
            # be a tuning knob silently ignored
            raise ValueError(
                f"bucket_elems={opts.bucket_elems} does not match the "
                f"ZeRO updater's bucket layout (cap {plan.bucket_elems}): "
                f"with reduce_in_update the tap plan comes from the "
                f"updater — pass the same value to "
                f"zero2_sgd(bucket_elems=)")
    else:
        plan = BucketPlan.for_tree(params, opts.bucket_elems)
        chunks = collective = None

    def x_at(i):
        return jax.tree.map(lambda a: a[i], xs)

    auxes, prev = [], []
    for mi in range(n - 1):
        with jax.named_scope(scopes.LOSS_GRAD):
            (_, (carry, aux)), g = grad_of(params, carry, x_at(mi),
                                           jnp.int32(mi))
        auxes.append(aux)
        prev.append(jax.tree.leaves(g))
    extras = emulate_fn = None
    if n > 1:
        # the taps apply leaf_pre and the pressure to the LAST
        # micro-batch's cotangent only; the earlier ones get both here
        # (elementwise, so bit for bit the monolith's stacked pass)
        extras = []
        for i in range(len(plan.sizes)):
            st = jnp.stack([leaves[i] for leaves in prev])
            if leaf_pre is not None:
                st = leaf_pre(st, i)
            extras.append(st if sat is None else st * sat)
        emulate_fn = make_overlap_emulate_fn(
            n, opts.use_aps, opts.grad_exp, opts.grad_man, opts.stochastic)
    x_last, before_last = x_at(n - 1), carry
    (_, (carry, aux)), grads, report = overlapped_grads(
        lambda p: loss_of(p, before_last, x_last, jnp.int32(n - 1)), params,
        axis_name=axis_dp, plan=plan, reduce_kw=opts.reduce_kw(),
        key=sum_key, sat_factor=sat, wire_fault=wire_fault,
        verify=opts.verify_reduce, stats=opts.quant_stats,
        leaf_pre=leaf_pre, collective=collective, extras=extras,
        emulate_reduce=emulate_fn,
        emulate_key=_emulate_key(step, axis_dp, opts) if n > 1 else None)
    # stacked like the scan's outputs, so a builder's sums associate alike
    aux = jax.tree.map(lambda *a: jnp.stack(a), *auxes, aux)
    if collective is None:
        return Reduced(grads, carry, aux, report, {})
    return Reduced(extract_bucket_shards(grads, plan, chunks), carry, aux,
                   None, {"pre_sharded": True})


def report_metrics(report: Optional[dict], opts: ReduceOptions) -> dict:
    """The reduction's report as the step's replicated float32 metrics:
    the wire-integrity verdict and the numeric-health telemetry of THIS
    step's reduce, read by the transport and precision supervisors."""
    if report is None:
        return {}
    names = {}
    if opts.verify_reduce:
        names.update(reduce_ok="ok", reduce_hop_bad="hop_bad",
                     reduce_gather_bad="gather_bad", reduce_agree="agree")
    if opts.quant_stats:
        names.update(prec_wire_sat="wire_sat",
                     prec_wire_underflow="wire_underflow",
                     prec_wire_nan="wire_nan", prec_wire_total="wire_total",
                     prec_aps_bad="aps_bad")
    return {name: report[field].astype(jnp.float32)
            for name, field in names.items()}
