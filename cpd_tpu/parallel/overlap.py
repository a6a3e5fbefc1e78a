"""Overlapped backward-reduce: bucketed, dependency-scheduled gradient
transport (ISSUE 8; MLPerf TPU-pod bucketed gradient summation,
PAPERS.md #4).

Every reduction mode used to fire only after the ENTIRE backward pass had
produced every gradient: the micro-batch ``lax.scan`` in the step builders
emits all grads together, and the ring path additionally concatenates the
whole tree into ONE flat vector — both are hard barriers, so XLA could
never start a single collective hop while backward compute was still
running.  This module removes the barrier:

* :func:`bucket_layout` — the ONE greedy bucket-capping function, shared
  with ``dist._faithful_quantized_sum`` so the overlapped and the
  post-backward bucketed paths can never disagree about the layout;
* :class:`BucketPlan` — the static layout (leaf sizes, global flat
  offsets in parallel/dist.py's `_leaf_starts` space, bucket membership)
  plus a hashable ``key()`` for step-table cache keys
  (resilience/precision.ladder_step_key's ``overlap`` coordinate);
* :func:`overlapped_grads` — ``value_and_grad`` with per-bucket
  ``jax.custom_vjp`` taps on the parameters: each bucket's tap is an
  identity on the forward pass, and its BACKWARD rule runs that bucket's
  quantized all-reduce (`dist.sum_gradients` on the bucket's sub-tree,
  with the bucket's GLOBAL flat offsets) the moment autodiff closes the
  bucket's last cotangent.  Late-layer buckets therefore finish their
  reduction work while early-layer backward compute is still pending —
  the dependency structure XLA's scheduler needs to overlap ring hops
  with backward compute.  Verification / telemetry reports ride OUT of
  the backward through the tap-cotangent channel (the
  quant_function.quantizer_stats idiom): a zeros ``(n_buckets, R)``
  input whose "gradient" is defined by the tap's bwd rule to be the
  bucket's report vector;
* :func:`overlap_evidence` — the crude overlap-actually-happened
  assertion for CI: walks the traced step's jaxpr and counts matmul/conv
  equations scheduled AFTER the first reduction collective.  The
  monolithic step has none (every collective postdates all compute); the
  tapped step interleaves them — a structural property of the emitted
  program, not a timing flake.

Bitwise contract: the overlapped result equals the non-overlapped one
bit for bit.  The ordered quantized accumulation is elementwise across
ranks, SR bits are indexed by GLOBAL flat offset, and Kahan compensation
is per-element — so faithful/fast results are invariant to ANY bucket
layout, and ring results are invariant to overlap on/off at a FIXED
layout (``sum_gradients(mode="ring", bucket_elems=...)`` runs the same
per-bucket rings post-backward; tests/test_overlap.py gates all of it).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np

from ..obs import scopes

__all__ = ["bucket_layout", "BucketPlan", "overlapped_grads",
           "overlap_evidence", "evidence_from_prims",
           "extract_bucket_shards", "REPORT_FIELDS",
           "DEFAULT_BUCKET_ELEMS"]

# One home for the default per-bucket element cap (dist.py re-exports it
# as the faithful path's historical `_BUCKET_ELEMS`): W x 4M x 4B =
# 128 MiB of gathered fp32 at W=8 — large enough to amortize collective
# launch overhead, small enough that a bucket never rivals model memory
# AND late buckets close early enough in the backward to overlap.
DEFAULT_BUCKET_ELEMS = 4 * 1024 * 1024

# Fixed slot order of the per-bucket report vector that rides the
# tap-cotangent channel (float32; ints ride exactly up to 2^24).  The
# wire layout prepends one internal "ran" slot (always 1 when the tap's
# bwd executed): a bucket whose parameters the loss never touches has
# its tap dead-code-eliminated by autodiff — its gradients are zeros
# either way (reducing zeros yields zeros bitwise, so the data path is
# unaffected), but its report row stays all-zero, and without the
# sentinel the merged `agree` verdict would read a never-run bucket as
# a cross-replica DISAGREEMENT (a permanent false-positive that would
# livelock the transport ladder).
REPORT_FIELDS = ("hop_bad", "gather_bad", "agree", "wire_sat",
                 "wire_underflow", "wire_nan", "wire_total", "aps_bad")


def bucket_layout(sizes: Sequence[int], bucket_elems: int,
                  group_ids: Optional[Sequence] = None) -> list:
    """Greedy bucket capping: split leaf indices into buckets of at most
    ``bucket_elems`` total elements (a single leaf larger than the cap
    forms its own bucket), preserving leaf order.  ``group_ids`` (e.g.
    dtypes) force a bucket break between unequal neighbors — the faithful
    gather path buckets per dtype because the gathered stack must be one
    array.  This is THE layout function: `dist._faithful_quantized_sum`,
    the bucketed ring and the overlap taps all call it, so their bucket
    boundaries cannot drift."""
    if bucket_elems < 1:
        raise ValueError(f"bucket_elems must be >= 1, got {bucket_elems}")
    buckets: list = []
    cur: list = []
    cur_n = 0
    cur_gid = None
    for i, n in enumerate(sizes):
        gid = None if group_ids is None else group_ids[i]
        if cur and (cur_n + n > bucket_elems or gid != cur_gid):
            buckets.append(cur)
            cur, cur_n = [], 0
        cur.append(i)
        cur_n += int(n)
        cur_gid = gid
    if cur:
        buckets.append(cur)
    return buckets


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Static bucket layout over one gradient pytree.

    ``starts`` are GLOBAL flat offsets in tree_flatten order — the same
    index space `dist._leaf_starts` defines and the SR bitstream is
    indexed by, so a bucket's reduction draws exactly the bits the
    whole-tree reduction would."""
    sizes: tuple
    starts: tuple
    buckets: tuple          # tuple of tuples of leaf indices
    bucket_elems: int

    @classmethod
    def for_tree(cls, tree: Any, bucket_elems: Optional[int] = None,
                 group_by_dtype: bool = False) -> "BucketPlan":
        be = (DEFAULT_BUCKET_ELEMS if bucket_elems is None
              else int(bucket_elems))
        if be < 1:
            # fail HERE, at plan construction, not from bucket_layout
            # deep inside jit tracing of a per-bucket reduce
            raise ValueError(f"bucket_elems must be >= 1, got {be}")
        leaves = jax.tree_util.tree_leaves(tree)
        sizes = tuple(int(l.size) for l in leaves)
        starts = tuple(int(s) for s in
                       np.concatenate([[0], np.cumsum(sizes[:-1])])
                       ) if sizes else ()
        gids = ([str(jnp.dtype(l.dtype)) for l in leaves]
                if group_by_dtype else None)
        buckets = tuple(tuple(b) for b in bucket_layout(sizes, be, gids))
        return cls(sizes=sizes, starts=starts, buckets=buckets,
                   bucket_elems=be)

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    def key(self) -> tuple:
        """Hashable layout fingerprint for step-table cache keys: a step
        traced for one layout must never be served for another (the PR 5
        half-keyed-table bug class, now with a bucket coordinate)."""
        return (self.bucket_elems, self.buckets)


def _f0(x):
    """A float0 zero cotangent for a non-differentiable (integer) tap
    input — the tangent type JAX requires for int-dtype primals."""
    return np.zeros(np.shape(x), jax.dtypes.float0)


def _make_bucket_tap(reduce_bucket: Callable, n_leaves: int):
    """One identity tap per bucket: ``tap(z, keys, aux, *leaves,
    *extras)`` returns the leaves unchanged; its bwd rule reduces the
    leaf cotangents with `reduce_bucket` and returns the bucket's report
    vector as ``z``'s cotangent.  ``keys`` ((2, 2) uint32 — the [sum,
    emulate] PRNG key pair, possibly dummies) and ``aux`` (float32
    [sat_scale, wf_code, wf_rank]) are traced per-step values that must
    ride as ARGUMENTS — custom_vjp cannot close over tracers.  The
    optional per-leaf ``extras`` (the emulate-node path's stacked prior
    micro-batch gradients, ISSUE 12 leg 3) ride the same way: pass-through
    residuals consumed by the bwd rule's local reduce, zero cotangents
    out (they are data, not params)."""

    @jax.custom_vjp
    def tap(z, keys, aux, *operands):
        return tuple(operands[:n_leaves])

    def fwd(z, keys, aux, *operands):
        return tuple(operands[:n_leaves]), (keys, aux,
                                            operands[n_leaves:])

    def bwd(res, cots):
        keys, aux, extras = res
        reduced, report = reduce_bucket(list(cots), list(extras), keys,
                                        aux)
        # slot 0 is the "ran" sentinel (see REPORT_FIELDS comment): it
        # distinguishes a clean all-zero report from a tap autodiff
        # never executed (all-unused bucket)
        report = jnp.concatenate([jnp.ones((1,), jnp.float32), report])
        return (report, _f0(keys), jnp.zeros_like(aux), *reduced,
                *[jnp.zeros_like(e) for e in extras])

    tap.defvjp(fwd, bwd)
    return tap


def extract_bucket_shards(reduced: Any, plan: "BucketPlan",
                          chunks: Sequence[int]) -> jnp.ndarray:
    """Pull the per-bucket reduce-scattered shards back out of the
    embedded leaf-cotangent encoding a ZeRO-2 tap collective emits
    (`parallel.zero._Zero2.make_tap_reduce`: bucket b's (c_b,) shard
    sits in the first c_b flat slots of its leaves, zeros after) and
    concatenate them into the rank's (S,) shard vector the updater's
    ``pre_sharded`` path consumes."""
    leaves = jax.tree_util.tree_leaves(reduced)
    segs = []
    for idxs, c in zip(plan.buckets, chunks):
        flat = (leaves[idxs[0]].reshape(-1) if len(idxs) == 1 else
                jnp.concatenate([leaves[i].reshape(-1) for i in idxs]))
        segs.append(flat[:c])
    return (jnp.concatenate(segs) if segs
            else jnp.zeros((0,), jnp.float32))


def overlapped_grads(loss_fn: Callable, params: Any, *,
                     axis_name, plan: BucketPlan,
                     reduce_kw: dict, key=None,
                     sat_factor=None, wire_fault=None,
                     verify: bool = False, stats: bool = False,
                     leaf_pre: Optional[Callable] = None,
                     collective: Optional[Callable] = None,
                     extras: Optional[Sequence] = None,
                     emulate_reduce: Optional[Callable] = None,
                     emulate_key=None):
    """``value_and_grad`` with per-bucket reduce-in-backward taps.

    loss_fn(params) -> (loss, aux) — the scalar loss and auxiliary
    outputs, exactly what the step builders pass to value_and_grad.
    Returns ``((loss, aux), reduced_grads, report)`` where
    ``reduced_grads`` is the FULLY REDUCED gradient pytree (bitwise equal
    to ``sum_gradients(local_grads, ...)`` of the non-overlapped step)
    and ``report`` is the merged verification/telemetry dict (None when
    both ``verify`` and ``stats`` are off).

    reduce_kw   → the `sum_gradients` precision/mode kwargs
                  (use_aps/grad_exp/grad_man/use_kahan/mode/rounding/
                  block_scale/block_size — the block-scaled ring wire
                  threads through unchanged, and because blocks are
                  chunk-local the per-bucket taps reproduce the
                  monolith's block boundaries exactly: overlap on/off
                  stays bitwise identical with block scaling on).
    key         → the shared reduction SR key (grad_sr_key site 1); the
                  same key reaches every bucket — bits are global-offset
                  indexed, so per-bucket draws equal the whole-tree draw.
    sat_factor  → traced 2^k saturation-pressure scale applied to each
                  cotangent BEFORE its bucket reduce (None = off; the
                  FaultPlan ``sat_pressure`` attack keeps firing under
                  the overlapped schedule).
    wire_fault  → traced ``(code, rank)`` ring wire fault.  Injected
                  into bucket 0 ONLY, so the deterministic chaos drills
                  keep their exact expected counter values (one flip →
                  hop_bad == 1) whatever the bucket count.
    leaf_pre    → optional ``fn(cotangent, leaf_index)`` run on each leaf
                  cotangent before the bucket reduce — the LM step's
                  sp/tp psums, which in the monolithic step run between
                  backward and the dp reduce.
    collective  → optional per-bucket collective override replacing the
                  `sum_gradients` call (ISSUE 12 leg 3: ZeRO-2's
                  per-bucket reduce-scatter, `zero._Zero2.make_tap_reduce`):
                  ``fn(bucket_index, leaf_indices, gs, key) -> outputs``
                  with outputs shaped like the bucket's leaves (the
                  shard-embedding contract).  Mutually exclusive with
                  verify/stats — the ZeRO updaters thread no reports.
    extras      → optional per-leaf operand list (aligned with the FULL
                  flattened param leaves): the emulate-node path's
                  stacked (N-1, *leaf) prior micro-batch gradients,
                  threaded through each tap as pass-through residuals so
                  the bwd-rule reduce can see them without closing over
                  tracers.
    emulate_reduce → optional ``fn(cotangent, extra, leaf_index,
                  emu_key) -> local_grad`` run per leaf AFTER leaf_pre
                  and the sat scale and BEFORE the bucket collective —
                  the rank-local emulate-node ordered reduce (stacks the
                  last micro-batch's cotangent under the prior ones).
                  Requires ``extras``.
    emulate_key → the rank-folded emulate-node SR key (site 0); rides
                  the taps next to `key` (slot 1 of the key pair).
    """
    from .dist import sum_gradients

    leaves_t, treedef = jax.tree_util.tree_flatten(params)
    if len(leaves_t) != len(plan.sizes):
        raise ValueError(f"BucketPlan built for {len(plan.sizes)} leaves, "
                         f"params have {len(leaves_t)}")
    if collective is not None and (verify or stats):
        raise ValueError("a custom bucket collective threads no "
                         "verify/stats report — the ZeRO paths reject "
                         "them upstream (make_train_step)")
    if emulate_reduce is not None and extras is None:
        raise ValueError("emulate_reduce needs the prior micro-batches' "
                         "stacked gradients via extras=")
    if extras is not None and len(extras) != len(leaves_t):
        raise ValueError(f"extras must align with the {len(leaves_t)} "
                         f"param leaves, got {len(extras)}")
    n_rep = len(REPORT_FIELDS)
    has_key = key is not None
    has_emu_key = emulate_key is not None
    want_report = verify or stats

    def make_reduce(b: int, idxs: tuple):
        fault_armed = wire_fault is not None and b == 0

        def reduce_bucket(gs, extras_b, keys, aux):
            # order matters and mirrors the monolith exactly: the sp/tp
            # psums FIRST, the 2^k sat-pressure scale on the post-psum
            # gradients second (scaling before the psum could overflow
            # a per-rank value whose psum'd sum the monolith keeps
            # finite — a bitwise divergence at the fp32 range edge),
            # the rank-local emulate-node reduce third (its input is
            # the scaled post-psum micro grads, mix.py:251-282), the
            # cross-device collective last
            if leaf_pre is not None:
                gs = [leaf_pre(g, i) for g, i in zip(gs, idxs)]
            if sat_factor is not None:
                gs = [g * aux[0] for g in gs]
            if emulate_reduce is not None:
                gs = [emulate_reduce(g, e, i,
                                     keys[1] if has_emu_key else None)
                      for g, e, i in zip(gs, extras_b, idxs)]
            sum_key = keys[0] if has_key else None
            if collective is not None:
                out = collective(b, idxs, gs, sum_key)
                return list(out), jnp.zeros((n_rep,), jnp.float32)
            wf = ((aux[1].astype(jnp.int32), aux[2].astype(jnp.int32))
                  if fault_armed else None)
            out = sum_gradients(
                list(gs), axis_name,
                key=sum_key,
                verify=verify, stats=stats, wire_fault=wf,
                offset_starts=[plan.starts[i] for i in idxs],
                **reduce_kw)
            if want_report:
                out, rep = out
                report = jnp.stack([
                    rep.get(f, jnp.zeros([], jnp.float32))
                    .astype(jnp.float32) for f in REPORT_FIELDS])
            else:
                report = jnp.zeros((n_rep,), jnp.float32)
            return out, report

        return reduce_bucket

    taps = [_make_bucket_tap(make_reduce(b, idxs), len(idxs))
            for b, idxs in enumerate(plan.buckets)]
    dummy = jnp.zeros((2,), jnp.uint32)
    keys = jnp.stack([jnp.asarray(key) if has_key else dummy,
                      jnp.asarray(emulate_key) if has_emu_key else dummy])
    aux = jnp.stack([
        (jnp.asarray(sat_factor, jnp.float32) if sat_factor is not None
         else jnp.float32(1.0)),
        (wire_fault[0].astype(jnp.float32) if wire_fault is not None
         else jnp.float32(0.0)),
        (wire_fault[1].astype(jnp.float32) if wire_fault is not None
         else jnp.float32(0.0))])

    def inner(p, z):
        leaves = list(jax.tree_util.tree_flatten(p)[0])
        for b, idxs in enumerate(plan.buckets):
            ext = ([extras[i] for i in idxs] if extras is not None
                   else [])
            outs = taps[b](z[b], keys, aux,
                           *[leaves[i] for i in idxs], *ext)
            for j, i in enumerate(idxs):
                leaves[i] = outs[j]
        return loss_fn(jax.tree_util.tree_unflatten(treedef, leaves))

    z0 = jnp.zeros((plan.n_buckets, n_rep + 1), jnp.float32)
    with jax.named_scope(scopes.LOSS_GRAD):
        (loss, aux_out), (g_params, g_z) = jax.value_and_grad(
            inner, argnums=(0, 1), has_aux=True)(params, z0)

    report = None
    if want_report and plan.n_buckets == 0:
        report = {"hop_bad": jnp.zeros([], jnp.int32),
                  "gather_bad": jnp.zeros([], jnp.int32),
                  "agree": jnp.ones([], jnp.int32),
                  "ok": jnp.ones([], jnp.int32)} if verify else {}
        if stats:
            report.update({f: jnp.zeros([], jnp.float32)
                           for f in ("wire_sat", "wire_underflow",
                                     "wire_nan", "wire_total")})
            report["aps_bad"] = jnp.zeros([], jnp.int32)
    elif want_report:
        ran = g_z[:, 0]
        cols = {f: g_z[:, i + 1] for i, f in enumerate(REPORT_FIELDS)}
        report = {}
        if verify:
            hop_bad = jnp.sum(cols["hop_bad"]).astype(jnp.int32)
            gather_bad = jnp.sum(cols["gather_bad"]).astype(jnp.int32)
            # a never-run bucket (ran == 0) reduced nothing — its wire
            # is vacuously clean, not a disagreement
            agree = jnp.min(jnp.where(ran > 0, cols["agree"], 1.0)
                            ).astype(jnp.int32)
            report.update(
                hop_bad=hop_bad, gather_bad=gather_bad, agree=agree,
                ok=((hop_bad == 0) & (gather_bad == 0)
                    & (agree == 1)).astype(jnp.int32))
        if stats:
            for f in ("wire_sat", "wire_underflow", "wire_nan",
                      "wire_total"):
                report[f] = jnp.sum(cols[f])
            # a never-run bucket's gradients are exact zeros; the
            # monolith's probe still CASTS and COUNTS them (zeros fit
            # every format: 0 sat/underflow/nan, n*W total).  Credit the
            # dead buckets' element counts so wire_total — the
            # precision supervisor's rate denominator — is identical
            # under either schedule.
            from jax import lax
            sizes_b = jnp.asarray(
                [sum(plan.sizes[i] for i in idxs)
                 for idxs in plan.buckets], jnp.float32)
            world = lax.psum(jnp.float32(1.0), axis_name)
            report["wire_total"] = report["wire_total"] + world * jnp.sum(
                jnp.where(ran > 0, 0.0, sizes_b))
            report["aps_bad"] = jnp.sum(cols["aps_bad"]).astype(jnp.int32)
    return (loss, aux_out), g_params, report


# ---------------------------------------------------------------------------
# overlap evidence (CI's crude "overlap actually happened" assertion)
# ---------------------------------------------------------------------------

# the gradient-TRANSPORT collectives: ppermute (ring hops), all_gather
# (gather path / ring rebuild) and all_to_all (ZeRO-2's per-bucket
# reduce-scatter, ISSUE 12).  psum is deliberately absent — scalar
# bookkeeping (world size, loss metrics) and the LM's FORWARD
# tensor-parallel psums would otherwise read as transport.
_COLLECTIVE_PRIMS = {"ppermute", "all_gather", "all_to_all"}
_COMPUTE_PRIMS = {"conv_general_dilated", "dot_general"}


def _walk_eqns(jaxpr, out: list):
    """Flatten a jaxpr's equations depth-first in emission order —
    equations are topologically ordered as traced, so relative positions
    reflect the dependency structure XLA schedules from.  Each entry is
    ``(primitive_name, max_operand_elems)``."""
    for eqn in jaxpr.eqns:
        size = 0
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                size = max(size, int(np.prod(aval.shape))
                           if aval.shape else 1)
        out.append((eqn.primitive.name, size))
        for v in eqn.params.values():
            if isinstance(v, jex_core.ClosedJaxpr):
                _walk_eqns(v.jaxpr, out)
            elif isinstance(v, jex_core.Jaxpr):
                _walk_eqns(v, out)
            elif isinstance(v, (tuple, list)):
                for w in v:
                    if isinstance(w, jex_core.ClosedJaxpr):
                        _walk_eqns(w.jaxpr, out)
                    elif isinstance(w, jex_core.Jaxpr):
                        _walk_eqns(w, out)
    return out


def evidence_from_prims(prims: Sequence,
                        min_collective_elems: int = 2) -> dict:
    """The ONE interleaving-count implementation, over an emission-order
    ``(primitive_name, max_operand_elems)`` stream (`_walk_eqns`'s
    output shape — the IR analyzer's program tracer feeds its own walk
    through here, analysis/ir/trace.py, so the CI gate and the lint
    rule cannot drift).  Collectives moving fewer than
    ``min_collective_elems`` elements are ignored — the world-size
    psum, loss/metric psums and the APS per-leaf exponent pmax are
    scalar bookkeeping, not gradient transport."""
    first_coll = None
    compute_positions = []
    n_coll = 0
    for i, (p, size) in enumerate(prims):
        if p in _COLLECTIVE_PRIMS and size >= min_collective_elems:
            n_coll += 1
            if first_coll is None:
                first_coll = i
        elif p in _COMPUTE_PRIMS:
            compute_positions.append(i)
    after = (0 if first_coll is None else
             sum(1 for i in compute_positions if i > first_coll))
    return {"collectives": n_coll,
            "compute_eqns": len(compute_positions),
            "compute_after_first_collective": after,
            "interleaved": after > 0}


def overlap_evidence(fn: Callable, *args,
                     min_collective_elems: int = 2) -> dict:
    """Trace ``fn(*args)`` and report how much matmul/conv compute the
    program is free to schedule AFTER its first payload-bearing
    reduction collective.

    ``compute_after_first_collective == 0`` means every gradient
    collective postdates all compute — the post-backward monolith (no
    overlap possible).  A positive count is the structural signature of
    the bucketed schedule: bucket k's ring hops are emitted while bucket
    k+1's backward matmuls are still pending, so the compiler MAY
    overlap them.  This checks the emitted dependency order, not
    wall-clock — a loaded CI box cannot flake it.  Every
    overlap-configured REGISTERED program is additionally gated on this
    verdict in CI by the ``ir-overlap`` analyzer rule
    (analysis/ir/rules.py), which shares `evidence_from_prims`."""
    prims = _walk_eqns(jax.make_jaxpr(fn)(*args).jaxpr, [])
    return evidence_from_prims(prims,
                               min_collective_elems=min_collective_elems)


def ir_programs(reg):
    """Program-contract declarations (analysis/ir/registry.py): a toy
    two-bucket overlapped_grads program and its post-backward monolith
    — the minimal schedule twins.  They claim bitwise parity
    (tests/test_overlap.py's whole matrix), so the `ir-schedule` rule
    pins their collective multisets equal; the `ir-overlap` rule pins
    the structural verdicts (taps interleave, monolith does not) — the
    registry-generalized form of `overlap_evidence`, gated in CI for
    every overlap-configured program rather than where a bench script
    happened to call the probe."""
    from jax.sharding import PartitionSpec as P

    from ..compat import shard_map
    from .mesh import data_parallel_mesh
    from .ring import ring_transport_bytes

    W, d = 8, 64
    n_leaf = d * d
    deps = ("cpd_tpu.parallel.overlap", "cpd_tpu.parallel.dist",
            "cpd_tpu.parallel.ring", "cpd_tpu.quant.numerics")
    reduce_kw = dict(mode="ring", grad_exp=5, grad_man=2)

    def _params():
        return {"w1": jnp.zeros((d, d), jnp.float32),
                "w2": jnp.zeros((d, d), jnp.float32)}

    def _wire():
        # two buckets (one per dxd leaf at cap n_leaf), each ringing
        # its own n_leaf-element flat — identical for taps and monolith
        return 2 * ring_transport_bytes(n_leaf, W, 5, 2)

    def _overlapped():
        def build():
            mesh = data_parallel_mesh()
            plan = BucketPlan.for_tree(_params(), n_leaf)

            def body(x):
                params = _params()

                def loss(p):
                    return jnp.sum((x[0] @ p["w1"]) @ p["w2"]), None

                (_, _), reduced, _ = overlapped_grads(
                    loss, params, axis_name="dp", plan=plan,
                    reduce_kw=dict(reduce_kw))
                return reduced

            fn = shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                           out_specs=P(), check_vma=False)
            return fn, (jax.ShapeDtypeStruct((W, 4, d), jnp.float32),)
        return build

    def _monolith():
        def build():
            from .dist import sum_gradients
            mesh = data_parallel_mesh()

            def body(x):
                params = _params()

                def loss(p):
                    return jnp.sum((x[0] @ p["w1"]) @ p["w2"])

                grads = jax.grad(loss)(params)
                return sum_gradients(grads, "dp",
                                     bucket_elems=n_leaf, **reduce_kw)

            fn = shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                           out_specs=P(), check_vma=False)
            return fn, (jax.ShapeDtypeStruct((W, 4, d), jnp.float32),)
        return build

    reg.declare("overlap.taps[ring,e5m2,w8]", _overlapped(),
                deps=deps, axis_sizes={"dp": W}, bitwise=True,
                twin="overlap.toy", overlap=True, wire=_wire)
    reg.declare("overlap.monolith[ring,e5m2,w8]", _monolith(),
                deps=deps, axis_sizes={"dp": W}, bitwise=True,
                twin="overlap.toy", overlap=False, wire=_wire)
