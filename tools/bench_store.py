"""Durable-store drill harness — the `store-smoke` CI gate (ISSUE 20).

Proves the crash-consistency contract of `cpd_tpu.store.DurableStore`
by actually killing processes at write boundaries, corrupting sealed
bytes, and rebuilding a whole serving fleet from the store after total
process death:

1. **crash matrix** (``--crash-matrix``, also inside ``--smoke``) —
   for each persistence surface shape (trainer checkpoint, engine
   snapshot, session capsule), a subprocess publishes generation B
   over an existing generation A with `FaultFS(crash_at_op=n)` for
   EVERY write-op stratum ``n`` of the publish (mkdir, each
   artifact write/fsync pair, the manifest pair, the tmp-dir fsync,
   the commit rename, the root fsync).  Gate, per stratum: the child
   exits with ``CRASH_EXIT`` exactly when it should; a fresh store's
   `newest_valid` always lands on a sealed, digest-valid generation;
   the restored bytes are BITWISE generation A for every stratum at or
   before the commit rename and bitwise B after it — never a blend,
   never a torn read; half-published tmp dirs are swept to quarantine
   and counted, never adopted.  The whole matrix runs twice and every
   per-stratum recovery counter must match exactly (x2).

2. **quarantine drill** — ``store_flip`` / ``store_torn`` chaos
   corrupts the two newest of three generations; the recovery scan
   quarantines both (counted, nothing deleted) and restores the
   oldest, still-valid one bitwise.  The number of VALID generations
   is never reduced by quarantine, and `gc` afterwards provably spares
   the newest valid generation.  Counters exact x2.

3. **transient-retry drill** — ``store_eio@s:n`` / ``store_enospc@s:n``
   mid-publish: the deterministic step-clock retry absorbs the fault
   (counted: ``io_errors``, ``publish_retries``, ``backoff_steps``,
   ``*_fired``); with the retry budget at zero the publish fails but
   the PREVIOUS generation stays restorable.  Unfired store specs are
   flagged in both directions (`DurableStore.report_unfired` and
   `resilience.inject.report_unfired(store_armed=...)`).

4. **fleet cold-restore drill** — a 2-engine `Fleet` with ``store=``
   serves real traffic, snapshots a round, and dies completely;
   `Fleet.cold_restore` rebuilds it from the newest valid consistent
   cut and drains.  Gate: every post-restore logits row is bitwise
   identical to an uninterrupted store-off run at (8, 23),
   `unresolved()` is empty, and the restore replays x2 with identical
   fleet AND store counters.

Run time ~60 s on a laptop CPU (the cold-restore drill's compiles
dominate).  No timing asserts, so a loaded CI runner cannot flake it.

    python tools/bench_store.py --smoke         # the CI gate
    python tools/bench_store.py --crash-matrix  # the full kill sweep
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile


def _ensure_multidevice():
    """The cold-restore drill serves on the 8-virtual-device CPU
    platform (same trick as tests/conftest.py) — set before jax
    imports.  The crash-matrix children never import jax at all."""
    if "--help" in sys.argv or "-h" in sys.argv:
        return
    plat = os.environ.get("JAX_PLATFORMS", "")
    if plat in ("", "cpu") and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_"
                                     "count=8").strip()
    # a CI gate that defaults to the CPU mesh says so on its first line
    print(f"# {os.path.basename(__file__)}: JAX_PLATFORMS="
          f"{os.environ.get('JAX_PLATFORMS') or '(unset: jax picks)'}",
          flush=True)


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _check(ok: bool, what: str, detail: str = "") -> bool:
    tag = "ok" if ok else "FAIL"
    print(f"[store-smoke] {tag}: {what}" + (f" ({detail})" if detail
                                            else ""))
    return ok


# the three persistence surfaces, by ARTIFACT SHAPE (names mirror what
# the real surfaces publish — checkpoint.py / engine.py / migrate.py);
# the matrix children use deterministic filler bytes so they never pay
# a jax import (~0.1 s per child instead of seconds)
SURFACES = {
    "checkpoint": ("state.npz", "tree.json"),
    "engine": ("pool.npy", "digests.npy", "state.json"),
    "capsule": ("state.json", "pages.npy", "digests.npy"),
}


def _blob(surface: str, name: str, gen: str, size: int = 96) -> bytes:
    """Deterministic filler bytes, distinct per (surface, artifact,
    generation) — parent and child derive the identical expectation."""
    out, ctr = b"", 0
    seed = f"{surface}/{name}/{gen}".encode()
    while len(out) < size:
        out += hashlib.sha256(seed + ctr.to_bytes(4, "big")).digest()
        ctr += 1
    return out[:size]


def _artifacts(surface: str, gen: str) -> dict:
    return {name: _blob(surface, name, gen)
            for name in SURFACES[surface]}


def run_crash_child(root: str, surface: str, crash_at: int) -> int:
    """The subprocess body: publish generation B over the seeded store
    with simulated power loss before write op ``crash_at`` (or none
    when ``crash_at`` is past the publish).  Pure stdlib imports."""
    from cpd_tpu.store import DurableStore, FaultFS

    fs = FaultFS(crash_at_op=crash_at)
    store = DurableStore(root, fs=fs)
    store.publish(_artifacts(surface, "B"), step=2,
                  meta={"surface": surface},
                  writer=store.acquire_writer())
    return 0


def _probe_total_ops(surface: str) -> int:
    """How many write ops one publish of this surface's artifact set
    costs — measured, not assumed, so the matrix never goes stale
    against the publish sequence."""
    from cpd_tpu.store import DurableStore

    with tempfile.TemporaryDirectory() as d:
        s = DurableStore(d)
        before = s.fs.ops
        s.publish(_artifacts(surface, "B"), step=2)
        return s.fs.ops - before


def crash_matrix() -> bool:
    """The kill-at-every-write-boundary sweep (module docstring #1).
    A CPU-tier gate: every child runs with JAX_PLATFORMS=cpu whatever the
    parent resolved, so it can never contend for a chip the parent holds."""
    from cpd_tpu.store import CRASH_EXIT, DurableStore

    ok = True
    for surface in SURFACES:
        total = _probe_total_ops(surface)
        # op indices: mkdir, (write+fsync) per artifact, manifest
        # write+fsync, tmp-dir fsync, rename (the commit), root fsync.
        # A crash at stratum n kills BEFORE op n executes, so the
        # rename has happened only for n >= total-1; n == total crashes
        # nowhere (the child completes).
        commit_op = total - 2
        runs = []
        for _rnd in range(2):
            strata = []
            for n in range(total + 1):
                with tempfile.TemporaryDirectory() as d:
                    root = os.path.join(d, "store")
                    DurableStore(root).publish(
                        _artifacts(surface, "A"), step=1,
                        meta={"surface": surface})
                    rc = subprocess.run(
                        [sys.executable, os.path.abspath(__file__),
                         "--crash-child", root, surface, str(n)],
                        env=dict(os.environ, JAX_PLATFORMS="cpu"),
                        capture_output=True).returncode
                    want_rc = CRASH_EXIT if n < total else 0
                    rec = DurableStore(root)   # the restarted process
                    info = rec.newest_valid()
                    blobs = rec.load(info) if info is not None else None
                    if blobs == _artifacts(surface, "A"):
                        outcome = "A"
                    elif blobs == _artifacts(surface, "B"):
                        outcome = "B"
                    else:
                        outcome = "corrupt"
                    want = "A" if n <= commit_op else "B"
                    # a crash after mkdir but before the commit rename
                    # leaves a half-written tmp dir: swept to
                    # quarantine, counted, never adopted
                    want_swept = 1 if 1 <= n <= commit_op else 0
                    row = (n, rc, outcome,
                           rec.counters["tmp_swept"],
                           rec.counters["quarantined"],
                           rec.counters["restores"])
                    strata.append(row)
                    ok &= _check(
                        rc == want_rc and outcome == want
                        and rec.counters["tmp_swept"] == want_swept
                        and len(rec.quarantined()) == want_swept
                        and rec.counters["quarantined"] == 0,
                        f"crash-matrix {surface} op {n}/{total}",
                        f"rc={rc} restored={outcome} want={want} "
                        f"swept={rec.counters['tmp_swept']}")
            runs.append(strata)
        ok &= _check(runs[0] == runs[1],
                     f"crash-matrix {surface} recovery counters exact x2")
    return ok


def drill_quarantine() -> bool:
    """Corrupt-the-newest chaos -> quarantine, fall back, never lose a
    valid generation (module docstring #2)."""
    from cpd_tpu.resilience.inject import FaultPlan
    from cpd_tpu.store import DurableStore

    ok = True
    runs = []
    for _rnd in range(2):
        with tempfile.TemporaryDirectory() as d:
            plan = FaultPlan.parse("store_flip@1:4,store_torn@2:8")
            s = DurableStore(d, fault_plan=plan)
            w = s.acquire_writer()
            arts = [_artifacts("engine", f"g{i}") for i in range(3)]
            for i in range(3):
                s.publish(arts[i], step=i, writer=w)  # 1 and 2 corrupted
            info = s.newest_valid()
            ok &= _check(info is not None and s.load(info) == arts[0],
                         "quarantine falls back to the valid generation "
                         "bitwise")
            ok &= _check(s.counters["quarantined"] == 2
                         and len(s.quarantined()) == 2
                         and s.counters["flip_fired"] == 1
                         and s.counters["torn_fired"] == 1,
                         "both corruptions fired and quarantined",
                         f"quarantined={s.quarantined()}")
            n_valid = len(s.valid_generations())
            ok &= _check(n_valid == 1,
                         "quarantine never reduces the valid-generation "
                         "count", f"valid={n_valid}")
            # two more publishes, then gc: the newest valid generation
            # is structurally uncollectable
            s.publish(_artifacts("engine", "g3"), step=3, writer=w)
            s.publish(_artifacts("engine", "g4"), step=4, writer=w)
            s.gc(keep=1)
            top = s.newest_valid()
            ok &= _check(top is not None
                         and s.load(top) == _artifacts("engine", "g4"),
                         "gc spares the newest valid generation")
            ok &= _check(s.report_unfired() == [],
                         "no store spec left pending")
            runs.append(dict(s.counters))
    ok &= _check(runs[0] == runs[1], "quarantine drill counters exact x2",
                 json.dumps({k: v for k, v in runs[0].items() if v}))
    return ok


def drill_transient() -> bool:
    """EIO/ENOSPC mid-publish: absorbed by the deterministic retry; a
    dead retry budget still leaves the previous generation restorable
    (module docstring #3)."""
    from cpd_tpu.resilience.inject import (FaultPlan, Injector,
                                           report_unfired)
    from cpd_tpu.store import DurableStore

    ok = True
    runs = []
    for _rnd in range(2):
        with tempfile.TemporaryDirectory() as d:
            plan = FaultPlan.parse("store_eio@1:3,store_enospc@2:2")
            s = DurableStore(d, fault_plan=plan)
            w = s.acquire_writer()
            for i in range(3):
                s.publish(_artifacts("capsule", f"g{i}"), step=i,
                          writer=w)
            info = s.newest_valid()
            ok &= _check(info is not None
                         and s.load(info) == _artifacts("capsule", "g2"),
                         "retried publishes land bitwise")
            ok &= _check(s.counters["eio_fired"] == 1
                         and s.counters["enospc_fired"] == 1
                         and s.counters["publish_retries"] == 2
                         and s.counters["io_errors"] == 2
                         and s.counters["backoff_steps"] == 2,
                         "transient faults counted exactly",
                         json.dumps({k: v for k, v in
                                     s.counters.items() if v}))
            runs.append(dict(s.counters))
    ok &= _check(runs[0] == runs[1], "transient drill counters exact x2")

    # retry budget zero: the publish FAILS, the previous generation
    # survives untouched
    with tempfile.TemporaryDirectory() as d:
        plan = FaultPlan.parse("store_enospc@1:2")
        s = DurableStore(d, retries=0, fault_plan=plan)
        w = s.acquire_writer()
        s.publish(_artifacts("capsule", "g0"), step=0, writer=w)
        failed = False
        try:
            s.publish(_artifacts("capsule", "g1"), step=1, writer=w)
        except OSError:
            failed = True
        info = s.newest_valid()
        ok &= _check(failed and info is not None
                     and s.load(info) == _artifacts("capsule", "g0"),
                     "exhausted retries leave the previous generation "
                     "restorable")

    # unfired honesty, both directions
    with tempfile.TemporaryDirectory() as d:
        plan = FaultPlan.parse("store_eio@7:1")
        s = DurableStore(d, fault_plan=plan)
        s.publish(_artifacts("capsule", "g0"), step=0)  # clock 0, not 7
        ok &= _check(len(s.report_unfired()) == 1,
                     "armed-but-never-reached store spec reported "
                     "unfired")
        inj = Injector(FaultPlan.parse("store_eio@7:1"))
        ok &= _check(len(report_unfired(inj, store_armed=False)) == 1
                     and report_unfired(inj, store_armed=True) == [],
                     "report_unfired(store_armed=) covers both "
                     "directions")
    return ok


def drill_cold_restore() -> bool:
    """Total fleet death -> `Fleet.cold_restore` -> bitwise drain
    (module docstring #4)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cpd_tpu.fleet import Fleet
    from cpd_tpu.models import transformer_lm
    from cpd_tpu.serve import Request
    from cpd_tpu.store import DurableStore

    VOCAB = 64
    kw = dict(n_slots=2, max_seq=32, page_size=8, prefill_chunk=4,
              record_logits=True, kv_format=(8, 23))
    model = transformer_lm(vocab_size=VOCAB, d_model=32, n_layers=2,
                           n_heads=4, n_kv_heads=2, d_ff=64)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]

    def reqs():
        out = []
        for i in range(4):
            rng = np.random.RandomState(i + 1)
            out.append(Request(
                rid=i,
                prompt=tuple(int(x) for x in rng.randint(0, VOCAB, 6)),
                max_new_tokens=6, sla_class=i % 2, arrival=0,
                deadline_steps=500))
        return out

    def rows(fleet):
        out = {}
        for e in fleet.engines:
            for rid, pos, row in e.logits_log:
                out[(rid, pos)] = row
        return out

    ok = True
    ref = Fleet(model, params, 2, engine_kw=kw)
    for r in reqs():
        ref.submit(r)
    ref.run_until_drained()
    ref_rows = rows(ref)

    runs = []
    for _rnd in range(2):
        with tempfile.TemporaryDirectory() as d:
            store = DurableStore(os.path.join(d, "plane"))
            fl = Fleet(model, params, 2, engine_kw=kw, store=store,
                       snapshot_every=4)
            for r in reqs():
                fl.submit(r)
            for _ in range(4):
                fl.step()          # the snapshot round seals at step 4
            del fl                 # total process death

            cold = Fleet.cold_restore(model, params, store,
                                      engine_kw=kw)
            ok &= _check(cold.step_index == 4
                         and cold.counters["cold_restores"] == 1,
                         "cold restore resumes at the consistent cut")
            cold.run_until_drained()
            ok &= _check(cold.unresolved() == [],
                         "zero silent drops across total death")
            got = rows(cold)
            bitwise = (len(got) > 0 and set(got) <= set(ref_rows)
                       and all((got[k].view(np.uint32)
                                == ref_rows[k].view(np.uint32)).all()
                               for k in got))
            ok &= _check(bitwise,
                         "post-restore decode bitwise equals the "
                         "uninterrupted run at (8,23)",
                         f"rows={len(got)}")
            runs.append((dict(cold.counters), dict(store.counters)))
    ok &= _check(runs[0] == runs[1],
                 "cold-restore fleet AND store counters exact x2")
    return ok


def run_smoke() -> int:
    from cpd_tpu.obs.timing import now
    t0 = now()
    ok = True
    ok &= crash_matrix()
    ok &= drill_quarantine()
    ok &= drill_transient()
    ok &= drill_cold_restore()
    print(json.dumps({"bench": "store", "smoke": bool(ok),
                      "secs": round(now() - t0, 1)}))
    return 0 if ok else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--smoke", action="store_true",
                   help="run the store-smoke CI gate drills")
    p.add_argument("--crash-matrix", action="store_true",
                   help="run only the kill-at-every-write-boundary "
                        "sweep")
    p.add_argument("--crash-child", nargs=3,
                   metavar=("ROOT", "SURFACE", "N"),
                   help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.crash_child:
        root, surface, n = args.crash_child
        return run_crash_child(root, surface, int(n))
    if args.crash_matrix:
        return 0 if crash_matrix() else 1
    if not args.smoke:
        p.error("pick --smoke (the CI gate) or --crash-matrix")
    return run_smoke()


if __name__ == "__main__":
    _ensure_multidevice()
    sys.exit(main())
