"""Deterministic fault injection — the chaos half of the resilience story.

A :class:`FaultPlan` is an immutable, seed-reproducible schedule of
:class:`FaultSpec` entries.  Two consumers execute it:

* :func:`with_fault_injection` — an optax wrapper that corrupts the
  gradients *inside* the jitted step (NaN / Inf / exponent blow-up,
  optionally on a single data-parallel shard via ``lax.axis_index`` to
  model one rank's corrupted quantized-reduce output).  The schedule is
  baked into the compiled program as a constant table indexed by the
  wrapper's own update counter, so injection is jit-compatible and
  bit-reproducible.  Note the counter lives in the optimizer state: a
  rollback that restores an old state REPLAYS the same faults — by
  design (same plan, same timeline).
* :class:`Injector` — the host-side driver for everything that is not a
  gradient: poisoning a float batch, dropping/duplicating a batch,
  stalling the host thread (straggler), truncating / bit-flipping a
  checkpoint file, raising mid-step (preemption), and inflating the
  observed loss (divergence-sentinel drill).  Host faults are
  **one-shot**: each spec fires once and is consumed, so a
  rollback-and-replay recovers instead of re-tripping forever.

Grammar for ``--fault-plan`` (also accepts a path to a JSON file written
by :meth:`FaultPlan.to_json`):

    kind@step[:arg[:arg2]][;kind@step[:arg[:arg2]]...]

e.g. ``grad_nan@3;stall@5:1.5;ckpt_truncate@6;loss_spike@8:1e6``.
``arg`` means: shard index for ``grad_*`` (-1 = every shard, the
default), RANK for ``wire_*`` (-1 = rank 0), the log2 scale factor for
``sat_pressure`` (-1 = 24, i.e. ×2^24), seconds for ``stall``,
multiplier for ``loss_spike`` / ``batch_scale``; ignored elsewhere.
``arg2`` only exists for the two-argument elastic kinds below (-1 =
kind-specific default).

A third executor consumes the ``wire_*`` kinds (``wire_flip@s:k``,
``wire_stale@s:k``, ``wire_drop@s:k``): the ring transport itself
(parallel/ring.py), which corrupts the bit-packed hop payload inside
its scan body and the all-gather wire on rank ``k`` at step ``s`` —
deterministic (same seed/plan ⇒ same corruption), detected by the
integrity checksums (parallel/integrity.py) when the reduce runs with
``verify=True``.  :meth:`FaultPlan.wire_schedule` compiles them into
the dense (codes, ranks) table the step builders bake in.

A fourth executor consumes ``sat_pressure@s:k`` (the scale-blowup
attack of the precision ladder, resilience/precision.py): the step
builders bake :meth:`FaultPlan.sat_schedule`'s dense exponent table
into the program and scale step ``s``'s LOCAL post-backward gradients
by ``2^k`` (default k=24) BEFORE the emulate-node reduce and the
quantized collective — an exact power-of-two, identical on every rank,
that deterministically drives the reduce-wire cast into saturation.
Schedule ``patience`` consecutive specs to force an escalation; the
same plan without the ladder is the degradation baseline (the grad
guard skips the saturated steps, or the loss blows up).

A fifth executor consumes ``kv_flip@s:k`` (the serving-side corruption
attack): the serving engine (cpd_tpu/serve/engine.py) flips one byte in
request slot ``k``'s first KV-cache page at ENGINE step ``s`` (held
until the slot holds cached K/V) — detected by the per-page digests and
repaired by recomputation without dropping the request
(docs/SERVING.md).  The engine does its own unfired accounting.

The same executor consumes the serving-chaos kinds (``SERVE_KINDS``,
ISSUE 10 — all on the serving engine's step clock):

* ``kv_storm@s:k`` — flip one byte in each of up to ``k`` (default 3)
  DISTINCT live KV pages at engine step ``s`` (held until at least one
  live page exists): multi-page corruption wide enough that the
  `ServeSupervisor` degradation ladder, not just the scrubber, has to
  react.
* ``slot_stall@s:k`` — request slot ``k`` stops making token progress
  from engine step ``s`` (held until the slot is decoding): a wedged
  decode lane, caught by the engine's no-progress watchdog, which
  evicts the slot's pages and re-prefills its cache from the host-held
  token history without dropping the request.
* ``req_burst@s:k`` — a flash crowd of ``k`` (default 4) extra requests
  arrives at engine step ``s``; the LOAD GENERATOR is the consumer
  (`serve.loadgen.run_trace(burst_factory=...)` pops the due specs via
  `ServeEngine.take_due_bursts`), so the burst is keyed into the plan
  and replays deterministically like every other fault.

A sixth executor consumes the elastic-training kinds (``ELASTIC_KINDS``,
ISSUE 19 — whole-host faults on the optimizer-update clock, consumed by
`cpd_tpu.resilience.elastic.run_elastic` / the trainers' ``--elastic``
path, which do their own one-shot + unfired accounting):

* ``host_kill@s:h[:r]`` — host ``h``'s heartbeat disappears at step
  ``s``; with ``arg2`` = ``r`` >= 0 it reappears ``r`` steps later (the
  regrow drill), -1 (default) = never.  The `ElasticSupervisor` drains
  the dead host and shrinks the mesh W -> W' deterministically.
* ``straggler@s:h:f`` — host ``h``'s step time at step ``s`` reads as
  inflated by factor ``f`` (arg2, -1 -> 4.0).  One spec = one slow
  heartbeat; schedule ``patience`` consecutive steps to force the
  detector hot (the ``sat_pressure`` idiom).
* ``link_flaky@s:h:p`` — the reduce wire into host ``h`` fails ``p``
  (arg2, -1 -> 1) consecutive attempts at step ``s``, plan-keyed
  deterministic; absorbed by the in-step collective retry when ``p``
  <= the supervisor's ``max_retries``, escalated to a drain+shrink
  otherwise.

A seventh executor consumes the storage-chaos kinds (``STORE_KINDS``,
ISSUE 20 — on the `cpd_tpu.store.DurableStore` PUBLISH clock, consumed
by any store built with ``fault_plan=``, which owns their one-shot +
unfired accounting):

* ``store_eio@s:n`` / ``store_enospc@s:n`` — transient EIO / ENOSPC
  instead of the nth write-class I/O op of publish number ``s``,
  absorbed by the store's deterministic retry-with-backoff.
* ``store_torn@s:k`` / ``store_flip@s:k`` — the generation publish
  ``s`` sealed is truncated at byte ``k`` / byte-flipped at offset
  ``k`` (-1 -> the legacy half-size / midpoint defaults), through the
  same `store.faultfs.corrupt_file` body as ``ckpt_truncate`` /
  ``ckpt_bitflip``; detected by the manifest digests, quarantined,
  never adopted.

``step`` convention: the 0-based optimizer-UPDATE index — one clock for
both executors, so ``grad_nan@3`` and ``stall@3`` hit the same physical
step in every entry point (run_guarded and both trainer CLIs).  The
``ckpt_*`` kinds are the exception: their step is the saved
checkpoint's own step number (what ``restore_latest_valid`` sees),
because that is the name the corruption must land on; ``kv_flip``'s
step is the serving engine's step clock.
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import time
from typing import Any, Iterable, NamedTuple, Optional

import numpy as np

__all__ = ["FaultSpec", "FaultPlan", "Injector", "InjectedPreemption",
           "with_fault_injection", "report_unfired", "GRAD_KINDS",
           "HOST_KINDS", "WIRE_KINDS", "SAT_KINDS", "KV_KINDS",
           "SERVE_KINDS", "FLEET_KINDS", "ELASTIC_KINDS", "STORE_KINDS",
           "SAT_PRESSURE_DEFAULT_EXP"]

# jit-level kinds -> corruption opcode in the compiled fault table
GRAD_KINDS = {"grad_nan": 1, "grad_inf": 2, "grad_blowup": 3}
# wire-level kinds -> corruption opcode inside ring_quantized_sum
# (parallel/ring.py _apply_hop_fault / the gather-wire fault)
WIRE_KINDS = {"wire_flip": 1, "wire_stale": 2, "wire_drop": 3}
# saturation-pressure kind, executed by the gradient stage's baked 2^k
# gradient-scale table (train/grads.py, the builders' sat_fault_plan) —
# the attack the precision ladder is exercised against
SAT_KINDS = frozenset({"sat_pressure"})
SAT_PRESSURE_DEFAULT_EXP = 24          # arg -1 -> scale by 2^24
# KV-cache corruption kind, executed by the serving engine
# (serve/engine.py): ``kv_flip@s:k`` flips one byte in request slot
# ``k``'s first KV page at engine step ``s`` (held until that slot holds
# cached K/V) — the corruption class the per-page digests detect and the
# repair-by-recompute ladder absorbs without dropping the request.
# ``step`` here is the ENGINE-step clock, not the optimizer-update clock.
KV_KINDS = frozenset({"kv_flip"})
# serving-chaos kinds (ISSUE 10), all on the serving engine's step
# clock: ``kv_storm@s:k`` (byte flips in up to k DISTINCT live pages —
# wide enough to exercise the ServeSupervisor degradation ladder, not
# just the scrubber), ``slot_stall@s:k`` (slot k stops making token
# progress until the engine's no-progress watchdog evicts and
# re-prefills it from history), and ``req_burst@s:k`` (k extra requests
# arrive at step s — consumed by the load generator through
# `ServeEngine.take_due_bursts`, so the flash crowd is keyed into the
# plan and replays deterministically).
SERVE_KINDS = frozenset({"kv_storm", "slot_stall", "req_burst"})
# fleet-chaos kinds (ISSUE 13, 17), on the FLEET step clock (which is
# also every member engine's step clock — the fleet steps them in
# lockstep):
# ``engine_kill@s:e`` kills engine ``e`` of a `cpd_tpu.fleet.Fleet` at
# fleet step ``s`` — the fleet recovers the engine's state from its
# last periodic snapshot plus the deterministic submission replay log,
# then DRAINS it (queued work re-routed, live sessions migrated out
# where capacity allows, the rest completing locally with admissions
# closed) with zero silent drops.  A kill aimed at an index the fleet
# shape never contained (possible under autoscaling) is held, never
# re-aimed, and surfaces through `Fleet.report_unfired`.
# ``kill_wave@s:c`` (ISSUE 17) is the coordinated multi-engine kill: up
# to ``c`` (default 2) accepting engines die at fleet step ``s`` —
# admissions close on every victim before any drain migration runs, at
# least one accepting survivor always remains, and any shortfall is
# counted (``kill_wave_shortfall``), never silent.  The fleet does its
# own unfired accounting (`Fleet.report_unfired`); in a plain training
# or single-engine serving plan these kinds can never fire and
# `report_unfired` flags them unless ``fleet_armed=True``.
FLEET_KINDS = frozenset({"engine_kill", "kill_wave"})
# elastic-training kinds (ISSUE 19), on the optimizer-update clock like
# the grad/wire kinds — but consumed by the ELASTIC harness
# (resilience/elastic.py run_elastic, or a trainer's ``--elastic``
# path), never by the plain Injector hooks: ``host_kill@s:h[:r]``
# (host h's heartbeat disappears at step s, reappearing r steps later
# when arg2 >= 0), ``straggler@s:h:f`` (host h's step time at step s
# inflated by f — one slow heartbeat per spec), ``link_flaky@s:h:p``
# (the reduce wire into host h fails p consecutive attempts at step s,
# absorbed by the in-step retry when p <= max_retries).  The harness
# does its own one-shot + unfired accounting; `report_unfired` flags
# these kinds in any run without an elastic consumer
# (``host_armed=False``, the default).
ELASTIC_KINDS = frozenset({"host_kill", "straggler", "link_flaky"})
# storage-chaos kinds (ISSUE 20), on the DurableStore's own PUBLISH
# clock (`cpd_tpu.store` counts publish calls across the whole store
# tree): ``store_eio@s:n`` / ``store_enospc@s:n`` raise a transient
# EIO / ENOSPC instead of executing the nth write-class I/O op of
# publish number ``s`` (one-shot — the store's deterministic
# retry-with-backoff must absorb it), ``store_torn@s:k`` truncates the
# largest artifact of the generation publish ``s`` sealed at byte ``k``
# (-1 -> the legacy half-size cut) and ``store_flip@s:k`` XOR-flips its
# byte ``k`` (-1 -> midpoint) — both through the SAME `corrupt_file`
# body as the legacy ``ckpt_truncate`` / ``ckpt_bitflip`` one-shots
# below, so the old checkpoint drills and the new storage drills share
# one injection body.  Only a `DurableStore` built with
# ``fault_plan=`` consumes these (it owns their one-shot + unfired
# accounting, `DurableStore.report_unfired`); in any run without a
# store attached they can never fire and `report_unfired` flags them
# unless ``store_armed=True``.
STORE_KINDS = frozenset({"store_torn", "store_flip", "store_eio",
                         "store_enospc"})
# host-level kinds, executed by the Injector around the step call
HOST_KINDS = frozenset({
    "batch_nan",       # poison one element of the first float batch leaf
    "batch_scale",     # multiply the float batch by `arg` (loss blow-up)
    "data_drop",       # this step's batch never arrives; use the next one
    "data_dup",        # the previous batch is delivered again
    "stall",           # sleep `arg` seconds mid-step (straggler)
    "preempt",         # raise InjectedPreemption before the step
    "ckpt_truncate",   # truncate the newest checkpoint's largest file
    "ckpt_bitflip",    # flip one byte in the newest checkpoint
    "loss_spike",      # multiply the observed loss metric by `arg`
})
_ALL_KINDS = (frozenset(GRAD_KINDS) | HOST_KINDS | frozenset(WIRE_KINDS)
              | SAT_KINDS | KV_KINDS | SERVE_KINDS | FLEET_KINDS
              | ELASTIC_KINDS | STORE_KINDS)


class InjectedPreemption(BaseException):
    """Simulated SIGTERM-mid-step.  Derives from BaseException so generic
    ``except Exception`` recovery code cannot accidentally swallow the
    preemption it is being tested against."""


@dataclasses.dataclass(frozen=True, order=True)
class FaultSpec:
    """One scheduled fault.  ``arg`` is kind-dependent (module
    docstring); ``arg2`` only carries the second argument of the
    two-argument elastic kinds (straggler factor, link attempt count,
    host-rejoin delay) and stays -1.0 everywhere else."""
    step: int
    kind: str
    arg: float = -1.0
    arg2: float = -1.0

    def __post_init__(self):
        if self.kind not in _ALL_KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}; know "
                             f"{sorted(_ALL_KINDS)}")
        if self.step < 0:
            raise ValueError(f"fault step must be >= 0, got {self.step}")


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """An immutable schedule of faults; equality/ordering is structural,
    so 'same seed + config => identical plan' is testable directly."""
    faults: tuple = ()
    seed: int = 0

    def __post_init__(self):
        object.__setattr__(self, "faults",
                           tuple(sorted(self.faults)))

    def __len__(self) -> int:
        return len(self.faults)

    # -- constructors -----------------------------------------------------

    @classmethod
    def parse(cls, text: str, seed: int = 0) -> "FaultPlan":
        """Parse the compact ``kind@step[:arg[:arg2]]`` grammar, or load
        a JSON file if ``text`` names one (the ``--fault-plan`` flag
        accepts both)."""
        text = text.strip()
        if not text:
            return cls((), seed)
        if os.path.exists(text):
            with open(text) as f:
                return cls.from_json(f.read())
        faults = []
        for part in text.replace(",", ";").split(";"):
            part = part.strip()
            if not part:
                continue
            try:
                kind, rest = part.split("@", 1)
                fields = rest.split(":", 2)
                if len(fields) > 2 and kind.strip() not in ELASTIC_KINDS:
                    raise ValueError(
                        f"arg2 only exists for the elastic kinds "
                        f"{sorted(ELASTIC_KINDS)}")
                step_s = fields[0]
                arg = float(fields[1]) if len(fields) > 1 else -1.0
                arg2 = float(fields[2]) if len(fields) > 2 else -1.0
                faults.append(FaultSpec(int(step_s), kind.strip(), arg,
                                        arg2))
            except ValueError as e:
                raise ValueError(
                    f"bad fault spec {part!r} (want "
                    f"kind@step[:arg[:arg2]]): {e}") from e
        return cls(tuple(faults), seed)

    @classmethod
    def random(cls, seed: int, n_steps: int,
               rates: Optional[dict] = None) -> "FaultPlan":
        """Seed-deterministic random plan: each kind fires independently
        per step with probability ``rates[kind]`` (default: a light mix
        of gradient corruption and stalls)."""
        rates = rates or {"grad_nan": 0.02, "grad_blowup": 0.02,
                          "stall": 0.01}
        rng = random.Random(seed)
        faults = []
        for step in range(n_steps):
            for kind in sorted(rates):
                if rng.random() < rates[kind]:
                    arg = (rng.uniform(0.2, 1.0) if kind == "stall"
                           else -1.0)
                    faults.append(FaultSpec(step, kind, arg))
        return cls(tuple(faults), seed)

    @classmethod
    def from_json(cls, blob: str) -> "FaultPlan":
        doc = json.loads(blob)
        return cls(tuple(FaultSpec(f["step"], f["kind"],
                                   float(f.get("arg", -1.0)),
                                   float(f.get("arg2", -1.0)))
                         for f in doc["faults"]),
                   int(doc.get("seed", 0)))

    def to_json(self) -> str:
        return json.dumps({"seed": self.seed,
                           "faults": [dataclasses.asdict(f)
                                      for f in self.faults]}, indent=2)

    # -- consumers --------------------------------------------------------

    def counts(self) -> dict:
        out: dict = {}
        for f in self.faults:
            out[f.kind] = out.get(f.kind, 0) + 1
        return out

    def grad_faults(self) -> tuple:
        return tuple(f for f in self.faults if f.kind in GRAD_KINDS)

    def wire_faults(self) -> tuple:
        return tuple(f for f in self.faults if f.kind in WIRE_KINDS)

    def sat_faults(self) -> tuple:
        return tuple(f for f in self.faults if f.kind in SAT_KINDS)

    def kv_faults(self) -> tuple:
        """The serving engine's KV-page corruption specs (``arg`` is the
        target slot index, -1 -> slot 0)."""
        return tuple(f for f in self.faults if f.kind in KV_KINDS)

    def serve_faults(self) -> tuple:
        """The serving-chaos specs (`SERVE_KINDS`): ``kv_storm`` /
        ``slot_stall`` / ``req_burst`` — all on the serving engine's
        step clock (module docstring)."""
        return tuple(f for f in self.faults if f.kind in SERVE_KINDS)

    def fleet_faults(self) -> tuple:
        """The fleet-chaos specs (`FLEET_KINDS`): ``engine_kill@s:e``
        (``arg`` is the target engine index, -1 -> engine 0) and
        ``kill_wave@s:c`` (``arg`` is the victim count, -1 -> 2), both
        on the fleet step clock — consumed by
        `cpd_tpu.fleet.Fleet.step`."""
        return tuple(f for f in self.faults if f.kind in FLEET_KINDS)

    def elastic_faults(self) -> tuple:
        """The elastic-training specs (`ELASTIC_KINDS`):
        ``host_kill@s:h[:r]`` / ``straggler@s:h:f`` /
        ``link_flaky@s:h:p``, all on the optimizer-update clock —
        consumed by the elastic harness
        (`cpd_tpu.resilience.elastic.run_elastic` or a trainer's
        ``--elastic`` path), which owns their one-shot and unfired
        accounting."""
        return tuple(f for f in self.faults if f.kind in ELASTIC_KINDS)

    def store_faults(self) -> tuple:
        """The storage-chaos specs (`STORE_KINDS`):
        ``store_eio@s:n`` / ``store_enospc@s:n`` /
        ``store_torn@s:k`` / ``store_flip@s:k``, all on the
        `cpd_tpu.store.DurableStore` publish clock — consumed by a
        store built with ``fault_plan=``, which owns their one-shot and
        unfired accounting (`DurableStore.report_unfired`)."""
        return tuple(f for f in self.faults if f.kind in STORE_KINDS)

    def host_faults(self) -> dict:
        """step -> [FaultSpec] for the host-level kinds."""
        out: dict = {}
        for f in self.faults:
            if f.kind in HOST_KINDS:
                out.setdefault(f.step, []).append(f)
        return out

    def grad_schedule(self, n_steps: int):
        """Dense (codes, shards) int32 tables for the jit wrapper; entry
        ``i`` drives optimizer update ``i``.  At most one gradient fault
        per step (the last spec wins)."""
        codes = np.zeros((max(n_steps, 1),), np.int32)
        shards = np.full((max(n_steps, 1),), -1, np.int32)
        for f in self.grad_faults():
            if f.step < n_steps:
                codes[f.step] = GRAD_KINDS[f.kind]
                shards[f.step] = int(f.arg)
        return codes, shards

    def wire_schedule(self, n_steps: int):
        """Dense (codes, ranks) int32 tables for the ring transport's
        in-jit wire faults; entry ``i`` drives optimizer update ``i``
        (the same clock as `grad_schedule`).  ``arg`` is the target
        rank (-1 -> rank 0); at most one wire fault per step (the last
        spec wins).

        Bucketed / overlapped transports (``bucket_elems`` /
        ``overlap_reduce``, ISSUE 8): the table is still indexed by the
        optimizer-update clock — NOT by ring-call count — because the
        step builders bake ONE lookup per step and `sum_gradients`
        applies the fault to bucket 0 only (and, on a multi-axis mesh,
        to the single stage-0 ring whose other-axes indices are zero).
        A step's fault therefore fires exactly once however many
        per-bucket rings the schedule launches, keeping the chaos
        drills' exact counter expectations (one flip -> hop_bad == 1)
        and `report_unfired`'s fired/unfired accounting layout-free
        (covered in tests/test_overlap.py)."""
        codes = np.zeros((max(n_steps, 1),), np.int32)
        ranks = np.zeros((max(n_steps, 1),), np.int32)
        for f in self.wire_faults():
            if f.step < n_steps:
                codes[f.step] = WIRE_KINDS[f.kind]
                ranks[f.step] = max(int(f.arg), 0)
        return codes, ranks

    def sat_schedule(self, n_steps: int):
        """Dense int32 log2-scale table for the step builders' baked
        saturation-pressure attack (``sat_fault_plan=``); entry ``i``
        scales optimizer update ``i``'s local gradients by ``2^exps[i]``
        (0 = off — an exact no-op).  ``arg`` is the exponent (-1 ->
        `SAT_PRESSURE_DEFAULT_EXP`); at most one pressure per step (the
        last spec wins)."""
        exps = np.zeros((max(n_steps, 1),), np.int32)
        for f in self.sat_faults():
            if f.step < n_steps:
                exps[f.step] = (SAT_PRESSURE_DEFAULT_EXP if f.arg < 0
                                else int(f.arg))
        return exps


def sat_pressure_factor(table, step):
    """The 2^k gradient scale for optimizer update ``step`` from a dense
    `FaultPlan.sat_schedule` table — jit-safe, the ONE lookup, called
    by the gradient stage (train/grads.py) for every step builder.  Entry 0 -> 2^0 == 1.0, an
    exact fp32 no-op; steps past the table are unpressured."""
    import jax.numpy as jnp

    from ..parallel.aps import exp2_exact
    exps = jnp.asarray(table, jnp.int32)
    idx = jnp.clip(step, 0, exps.shape[0] - 1)
    e = jnp.where(step < exps.shape[0], exps[idx], 0)
    # exp2_exact, not jnp.exp2: the factor must be the EXACT power of
    # two the attack documents (XLA:CPU's exp2 is off by an ulp for
    # most negative integers — parallel/aps.py)
    return exp2_exact(e.astype(jnp.float32))


# ---------------------------------------------------------------------------
# jit-level gradient corruption (optax wrapper)
# ---------------------------------------------------------------------------

class FaultInjectState(NamedTuple):
    step: Any       # i32 update counter (drives the schedule table)
    injected: Any   # i32 faults fired so far
    inner: Any


def with_fault_injection(tx, plan: FaultPlan, n_steps: int, *,
                         axis_name: Optional[str] = None):
    """Wrap ``tx`` so incoming gradients are corrupted per ``plan``.

    Wrap OUTSIDE every defense under test
    (``with_fault_injection(with_grad_guard(...))``) so the corruption
    enters the pipeline exactly where a bad quantized reduce would.  With
    ``axis_name`` (inside shard_map) and a fault ``arg`` >= 0, only that
    shard's copy is corrupted — replicas now *disagree*, which is the
    failure mode the guard's cross-replica agreement check exists for.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    # a tuple (the guard's multi-axis agreement form) gates the shard
    # index on its FIRST axis — by convention the data axis, the one a
    # corrupted quantized reduce is per-replica over
    if isinstance(axis_name, (tuple, list)):
        axis_name = axis_name[0] if axis_name else None

    codes_np, shards_np = plan.grad_schedule(n_steps)

    def init(params):
        return FaultInjectState(jnp.zeros([], jnp.int32),
                                jnp.zeros([], jnp.int32), tx.init(params))

    def update(grads, state, params=None):
        codes = jnp.asarray(codes_np)
        shards = jnp.asarray(shards_np)
        idx = jnp.clip(state.step, 0, codes.shape[0] - 1)
        in_range = state.step < codes.shape[0]
        code = jnp.where(in_range, codes[idx], 0)
        shard = shards[idx]
        on = code > 0
        if axis_name is not None:
            me = lax.axis_index(axis_name).astype(jnp.int32)
            on = on & ((shard < 0) | (me == shard))

        def corrupt(g):
            flat = jnp.ravel(g).astype(g.dtype)
            nan_p = flat.at[0].set(jnp.nan)
            inf_p = flat.at[0].set(jnp.inf)
            blown = flat * jnp.asarray(2.0 ** 60, g.dtype)
            out = jnp.where(code == 1, nan_p,
                            jnp.where(code == 2, inf_p,
                                      jnp.where(code == 3, blown, flat)))
            return jnp.where(on, out, flat).reshape(g.shape)

        bad = jax.tree.map(corrupt, grads)
        updates, new_inner = tx.update(bad, state.inner, params)
        return updates, FaultInjectState(
            state.step + 1,
            state.injected + (code > 0).astype(jnp.int32),
            new_inner)

    import optax
    wrapped = optax.GradientTransformation(init, update)
    if getattr(tx, "norm_based", False):
        from ..train.optim import NormBasedTransformation
        wrapped = NormBasedTransformation(init, update)
    return wrapped


# ---------------------------------------------------------------------------
# host-level faults
# ---------------------------------------------------------------------------

def _poison_first_float_leaf(batch, value: float):
    """Return ``batch`` with element [0...] of its first float leaf set to
    ``value`` (NaN-poisoning a data batch — reference for how real bad
    records reach the loss).  Integer leaves (LM tokens, labels) are left
    alone."""
    import jax
    import numpy as np_  # local alias: keep module numpy pristine

    done = False

    def poke(leaf):
        nonlocal done
        arr = np_.asarray(leaf)
        if not done and np_.issubdtype(arr.dtype, np_.floating):
            arr = arr.copy()
            arr.reshape(-1)[0] = value
            done = True
            return arr
        return leaf

    out = jax.tree.map(poke, batch)
    if not done:
        raise ValueError("batch_nan fault: batch has no float leaf to "
                         "poison (LM token batches need a grad_* fault "
                         "instead)")
    return out


def _scale_float_leaves(batch, factor: float):
    import jax
    import numpy as np_

    def scale(leaf):
        arr = np_.asarray(leaf)
        if np_.issubdtype(arr.dtype, np_.floating):
            return arr * arr.dtype.type(factor)
        return leaf

    return jax.tree.map(scale, batch)


class Injector:
    """Executes a plan's host-level faults around a training loop.

    Each spec fires exactly once (consumed on fire) and is counted in
    ``fired``; ``log`` records the deterministic event sequence for the
    reproducibility assertion.  All decisions are pure functions of the
    plan — no wall clock, no RNG — so the same plan replays identically.
    """

    def __init__(self, plan: FaultPlan, rank: int = 0):
        self.plan = plan
        self.rank = rank
        self._pending = {step: list(specs)
                         for step, specs in plan.host_faults().items()}
        self.fired: dict = {}
        self.log: list = []

    def unfired(self) -> list:
        """Specs that never fired (scheduled past the end of the run, or
        on a hook the loop doesn't wire).  Loops report these at exit —
        a chaos run that silently skipped a fault proves nothing."""
        return sorted(f for specs in self._pending.values() for f in specs)

    def _take(self, step: int, kinds: Iterable[str]) -> Optional[FaultSpec]:
        specs = self._pending.get(step, [])
        for i, f in enumerate(specs):
            if f.kind in kinds:
                del specs[i]
                # each spec fires exactly once, so both records are
                # bounded by the static plan size (kind vocabulary /
                # one log entry per planned fault)
                self.fired[f.kind] = self.fired.get(f.kind, 0) + 1  # cpd: disable=host-unbounded -- keyed by the static fault-kind vocabulary
                self.log.append((f.kind, step))  # cpd: disable=host-unbounded -- one entry per planned fault; plans are finite by construction
                return f
        return None

    # -- hooks, in loop order --------------------------------------------

    def maybe_preempt(self, step: int) -> None:
        if self._take(step, ("preempt",)) is not None:
            raise InjectedPreemption(f"injected preemption at step {step}")

    def batch_action(self, step: int) -> Optional[str]:
        """'drop' / 'dup' / None — the loop owns the actual data motion."""
        f = self._take(step, ("data_drop", "data_dup"))
        if f is None:
            return None
        return "drop" if f.kind == "data_drop" else "dup"

    def corrupt_batch(self, step: int, batch):
        f = self._take(step, ("batch_nan", "batch_scale"))
        if f is None:
            return batch
        if f.kind == "batch_nan":
            return _poison_first_float_leaf(batch, float("nan"))
        return _scale_float_leaves(batch, f.arg if f.arg > 0 else 1e6)

    def maybe_stall(self, step: int) -> float:
        f = self._take(step, ("stall",))
        if f is None:
            return 0.0
        secs = f.arg if f.arg > 0 else 1.0
        time.sleep(secs)
        return secs

    def fault_loss(self, step: int, loss: float) -> float:
        f = self._take(step, ("loss_spike",))
        if f is None:
            return loss
        return loss * (f.arg if f.arg > 0 else 1e6)

    def corrupt_checkpoint(self, step: int, directory: str) -> bool:
        """Truncate or bit-flip the just-saved step's largest data file.
        Called by the loop right after a (finished) save at ``step``."""
        f = self._take(step, ("ckpt_truncate", "ckpt_bitflip"))
        if f is None:
            return False
        # ONE injection body for old and new storage drills (ISSUE 20):
        # the byte-level damage is `cpd_tpu.store.faultfs.corrupt_file`,
        # exactly what the `store_torn` / `store_flip` kinds use.
        from ..store.faultfs import corrupt_file
        step_dir = os.path.join(directory, str(step))
        if not os.path.isdir(step_dir):
            # a store-backed CheckpointManager keeps no per-step dir:
            # its checkpoints are DurableStore generations.  Aim at the
            # generation whose sealed manifest records this step.
            step_dir = self._store_generation_dir(directory, step)
        victim, size = None, -1
        for root, _, files in os.walk(step_dir):
            for name in sorted(files):
                p = os.path.join(root, name)
                s = os.path.getsize(p)
                if s > size:
                    victim, size = p, s
        if victim is None:
            raise FileNotFoundError(
                f"{f.kind} fault at step {step}: no checkpoint files "
                f"under {step_dir}")
        if f.kind == "ckpt_truncate":
            corrupt_file(victim, torn_at=-1)
        else:
            corrupt_file(victim, flip_at=-1)
        return True

    @staticmethod
    def _store_generation_dir(directory: str, step: int) -> str:
        """The ``gen-*`` directory of a `DurableStore`-backed checkpoint
        root whose manifest records ``step`` (newest first)."""
        best = os.path.join(directory, str(step))   # reported on miss
        for name in sorted(os.listdir(directory), reverse=True):
            if not name.startswith("gen-"):
                continue
            mpath = os.path.join(directory, name, "MANIFEST.json")
            try:
                with open(mpath) as fh:
                    if json.load(fh).get("step") == step:
                        return os.path.join(directory, name)
            except (OSError, ValueError):
                continue
        return best


def report_unfired(injector: Optional["Injector"], *, n_steps: Optional[int]
                   = None, meter=None, rank: int = 0,
                   wire_armed: bool = True,
                   sat_armed: bool = True,
                   kv_armed: bool = False,
                   serve_armed: bool = False,
                   fleet_armed: bool = False,
                   host_armed: bool = False,
                   store_armed: bool = False) -> list:
    """The ONE end-of-run check every loop calls: which planned faults
    never fired?  A chaos run that silently skipped a fault proves
    nothing — the usual causes are a plan step beyond the run's
    ``n_steps`` and a fault kind on a hook the run never wired, both
    silent user errors until this surfaces them.

    Covers the host-level one-shots (``Injector.unfired()``), the
    jit-level grad/wire/sat specs scheduled past the end of the compiled
    fault table (when ``n_steps`` is given — the schedule builders drop
    those without a sound), and — when the caller passes
    ``wire_armed=False`` / ``sat_armed=False`` — EVERY wire / sat spec,
    because the run's step never baked the corresponding table in
    (e.g. ``wire_flip`` planned for a faithful-mode run, or
    ``sat_pressure`` planned for a pp/moe run whose stepper takes no
    ``sat_fault_plan``; the trainers compute both from their config).
    ``kv_armed`` defaults False: the ``kv_flip`` kind only exists on the
    serving engine's clock (which does its OWN unfired accounting,
    `ServeEngine.report_unfired`), so a kv spec in a TRAINING plan is
    always a never-fires user error and is surfaced here.
    ``serve_armed`` defaults False for exactly the same reason: the
    `SERVE_KINDS` (``kv_storm``/``slot_stall``/``req_burst``, ISSUE 10)
    also live on the serving engine's clock and do their own unfired
    accounting there — in a training plan they can never fire and are
    flagged here.  ``fleet_armed`` likewise covers `FLEET_KINDS`
    (``engine_kill``/``kill_wave``, ISSUE 13/17): only a
    `cpd_tpu.fleet.Fleet` consumes them (its own `Fleet.report_unfired`
    owns armed accounting — including kills aimed at engine indices the
    autoscaled fleet shape never contained), so in any other plan they
    are flagged.  ``host_armed`` covers `ELASTIC_KINDS`
    (``host_kill``/``straggler``/``link_flaky``, ISSUE 19): only an
    elastic consumer (`resilience.elastic.run_elastic`, or a trainer
    run with ``--elastic``) executes them and owns their one-shot +
    unfired accounting, so in a non-elastic run — the default — they
    can never fire and are flagged here.  ``store_armed`` covers
    `STORE_KINDS` (``store_torn``/``store_flip``/``store_eio``/
    ``store_enospc``, ISSUE 20): only a `cpd_tpu.store.DurableStore`
    built with ``fault_plan=`` consumes them (its own
    `DurableStore.report_unfired` owns the armed direction — a spec
    aimed at a publish number the run never reached stays pending
    there), so in any run without a store attached they are flagged.
    Bumps the meter's ``faults_unfired`` counter and warns on rank 0;
    returns the sorted leftover list (empty = every planned fault
    fired)."""
    if injector is None:
        return []
    leftover = list(injector.unfired())
    for f in (injector.plan.grad_faults() + injector.plan.wire_faults()
              + injector.plan.sat_faults() + injector.plan.kv_faults()
              + injector.plan.serve_faults()
              + injector.plan.fleet_faults()
              + injector.plan.elastic_faults()
              + injector.plan.store_faults()):
        if f.kind in KV_KINDS or f.kind in SERVE_KINDS \
                or f.kind in FLEET_KINDS or f.kind in ELASTIC_KINDS \
                or f.kind in STORE_KINDS:
            # engine/fleet/elastic-consumer kinds: the training
            # ``n_steps`` budget says nothing about them.  Unarmed ->
            # can never fire, flagged; armed -> the consumer's own
            # accounting owns them.
            armed = (kv_armed if f.kind in KV_KINDS
                     else serve_armed if f.kind in SERVE_KINDS
                     else fleet_armed if f.kind in FLEET_KINDS
                     else store_armed if f.kind in STORE_KINDS
                     else host_armed)
            if not armed:
                leftover.append(f)
            continue
        past = n_steps is not None and f.step >= n_steps
        unwired = ((not wire_armed and f.kind in WIRE_KINDS)
                   or (not sat_armed and f.kind in SAT_KINDS))
        if past or unwired:
            leftover.append(f)
    leftover = sorted(set(leftover))
    if leftover:
        if meter is not None:
            meter.bump("faults_unfired", len(leftover))
        if rank == 0:
            import sys
            print(f"=> fault plan: {len(leftover)} spec(s) never fired "
                  f"(scheduled past the end of the run, or on a hook "
                  f"this loop does not wire): {leftover}",
                  file=sys.stderr)
    return leftover
