"""Abstract tracing + fact extraction for the program-contract rules.

``trace_program`` traces one `ProgramSpec` to its jaxpr with
``jax.make_jaxpr`` (abstract — no compile, no execute) and distills the
serializable **facts** the rules consume:

* ``collectives`` — the transport-collective schedule: one entry per
  ``ppermute``/``all_gather``/``all_to_all`` equation with its axis
  names, payload ``(shape, dtype)`` list and its TRIP COUNT (a
  collective inside a ``lax.scan`` body executes ``length`` times per
  enclosing trip; nested scans multiply).  ``psum`` is deliberately not
  transport — scalar bookkeeping and forward tensor-parallel reductions
  would otherwise read as gradient wire (same doctrine as
  `overlap.overlap_evidence`).
* ``transport_bytes`` — per-device bytes the schedule puts on the wire:
  a ppermute sends its payload once per trip; an all_gather sends its
  (local) payload to W-1 peers; an all_to_all of a leading-axis-W array
  keeps 1/W local and sends the rest.  W comes from the spec's
  ``axis_sizes``.  A transport collective under a ``while`` (unknown
  trip count) or on an undeclared axis flips ``unpriceable`` — the
  ledger rule reports it rather than guessing.
* ``prims`` — primitive census with trip-count multiplicity (the
  bitwise-stability rule's input).
* ``evidence`` — `overlap.evidence_from_prims` over the emission-order
  stream: the ONE interleaving implementation, shared with
  `overlap_evidence`.
* ``cond_divergent`` — ``cond`` equations whose branches carry UNEQUAL
  transport-collective multisets: the classic distributed deadlock/race
  shape (some replicas enter the collective, others never arrive).
* ``jaxpr_sha1`` — fingerprint of the printed jaxpr, the retrace
  probe's program identity.

All facts are plain JSON-serializable data, so the program cache
(run.py) can serve them without re-importing jax.
"""

from __future__ import annotations

import hashlib
from typing import Optional

from .registry import ProgramSpec

__all__ = ["TracedProgram", "trace_program", "TRANSPORT_PRIMS",
           "schedule_counter"]

# must stay equal to overlap._COLLECTIVE_PRIMS (asserted in tests): one
# definition of "transport collective" across the evidence probe and
# the IR rules
TRANSPORT_PRIMS = ("ppermute", "all_gather", "all_to_all")


class TracedProgram:
    """One program's extracted facts (or its trace failure)."""

    def __init__(self, spec: ProgramSpec, facts: Optional[dict] = None,
                 error: Optional[str] = None):
        self.spec = spec
        self.facts = facts
        self.error = error

    @property
    def ok(self) -> bool:
        return self.error is None


def _iter_jaxprs(v):
    import jax.extend.core as jc
    if isinstance(v, jc.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jc.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for w in v:
            yield from _iter_jaxprs(w)


def _aval_info(v):
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return None
    import numpy as np
    shape = tuple(int(s) for s in aval.shape)
    return (shape, str(aval.dtype),
            int(np.prod(shape)) if shape else 1,
            int(aval.dtype.itemsize))


def _axis_names(params) -> tuple:
    ax = params.get("axis_name", params.get("axes"))
    if ax is None:
        return ()
    if isinstance(ax, (tuple, list)):
        return tuple(str(a) for a in ax)
    return (str(ax),)


def _walk(jaxpr, mult: int, in_while: bool, in_cond: bool, state: dict):
    """Emission-order walk (the traversal `overlap._walk_eqns` uses),
    carrying the scan trip multiplier and inside-while/-cond flags."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        infos = [i for i in map(_aval_info, eqn.invars) if i is not None]
        max_elems = max((i[2] for i in infos), default=0)
        state["stream"].append((name, max_elems))
        state["prims"][name] = state["prims"].get(name, 0) + mult
        if name in TRANSPORT_PRIMS:
            state["collectives"].append({
                "kind": name,
                "axes": list(_axis_names(eqn.params)),
                "payload": [[list(i[0]), i[1]] for i in infos],
                "bytes": sum(i[2] * i[3] for i in infos),
                "mult": mult,
                "in_while": in_while,
                "in_cond": in_cond,
            })
        if name == "cond":
            branches = []
            for br in eqn.params.get("branches", ()):
                sub = {"stream": [], "prims": {}, "collectives": [],
                       "conds": []}
                for j in _iter_jaxprs(br):
                    _walk(j, 1, in_while, True, sub)
                branches.append(sub["collectives"])
            counters = [schedule_counter(b) for b in branches]
            if any(c != counters[0] for c in counters[1:]):
                state["conds"].append({
                    "branches": [sorted(str(k) for k in c) for c in
                                 counters]})
            # the generic params walk below ALSO descends into the
            # branches for the main census/evidence; their collectives
            # carry in_cond=True, which the byte ledger refuses to
            # price (only one branch runs — counting both would lie)
        inner_mult = mult
        inner_while = in_while
        inner_cond = in_cond or name == "cond"
        if name == "scan":
            inner_mult = mult * int(eqn.params.get("length", 1))
        elif name == "while":
            inner_while = True
        for v in eqn.params.values():
            for j in _iter_jaxprs(v):
                _walk(j, inner_mult, inner_while, inner_cond, state)


def schedule_counter(collectives) -> dict:
    """The schedule multiset: ``(kind, axes, payload) -> total trips``.
    Trip-count aggregation makes a scanned hop loop and its unrolled
    twin compare equal — the wire they move is identical."""
    out: dict = {}
    for c in collectives:
        key = (c["kind"], tuple(c["axes"]),
               tuple((tuple(s), d) for s, d in
                     (tuple(p) for p in c["payload"])))
        out[key] = out.get(key, 0) + c["mult"]
    return out


def _transport_bytes(collectives, axis_sizes) -> tuple:
    """(per-device bytes, unpriceable?) for the extracted schedule."""
    total = 0
    unpriceable = False
    for c in collectives:
        if c["in_while"] or c.get("in_cond"):
            unpriceable = True
            continue
        w = 1
        known = True
        for a in c["axes"]:
            if not axis_sizes or a not in axis_sizes:
                known = False
                break
            w *= int(axis_sizes[a])
        if not known:
            unpriceable = True
            continue
        b = c["bytes"]
        if c["kind"] == "ppermute":
            sent = b
        elif c["kind"] == "all_gather":
            sent = b * (w - 1)
        else:                               # all_to_all
            sent = (b // w) * (w - 1) if w else 0
        total += sent * c["mult"]
    return total, unpriceable


def trace_program(spec: ProgramSpec) -> TracedProgram:
    """Trace one spec abstractly and extract its facts; any failure —
    build error, trace error, too few devices — is captured as the
    TracedProgram's ``error``, never raised (the ir-trace rule turns it
    into a finding; a silent skip is the one outcome forbidden)."""
    try:
        import jax
        from ..ir import registry as _reg
        if len(jax.devices()) < _reg.IR_WORLD:
            raise RuntimeError(
                f"IR tracing needs {_reg.IR_WORLD} virtual CPU devices, "
                f"have {len(jax.devices())} — jax was initialized "
                f"before ensure_cpu_devices() could size the platform")
        fn, args = spec.build()
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:  # noqa: BLE001 — every failure is a finding
        return TracedProgram(
            spec, error=f"{type(e).__name__}: {e}")
    state: dict = {"stream": [], "prims": {}, "collectives": [],
                   "conds": []}
    _walk(closed.jaxpr, 1, False, False, state)
    from cpd_tpu.parallel.overlap import evidence_from_prims
    evidence = evidence_from_prims(state["stream"])
    bytes_counted, unpriceable = _transport_bytes(
        state["collectives"], spec.axis_sizes)
    facts = {
        "name": spec.name,
        "collectives": state["collectives"],
        "transport_bytes": bytes_counted,
        "unpriceable": unpriceable,
        "prims": state["prims"],
        "evidence": evidence,
        "cond_divergent": state["conds"],
        "jaxpr_sha1": hashlib.sha1(
            str(closed.jaxpr).encode()).hexdigest(),
        "n_eqns": len(state["stream"]),
    }
    return TracedProgram(spec, facts=facts)
