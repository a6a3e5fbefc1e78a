"""Elastic-training drill harness — the `elastic-smoke` CI gate (ISSUE 19).

Four drills on the 8-virtual-device CPU mesh, all driving the REAL
stack end to end — `resilience.run_elastic` over a ZeRO-1 train step
(the pad_to_world re-flatten is on the recovery path), the real
CheckpointManager with integrity digests, and the plan-derived
heartbeat tables (no wall clock anywhere, so every drill is replayed
twice and must match event-for-event):

1. **host_kill shrink drill** — `host_kill@5:3` on W=8, ckpt cadence 2:
   the run drains host 3, shrinks to W'=4 on hosts (0,1,2,4), resumes
   from the sealed step-4 checkpoint and finishes.  Gate: the
   post-shrink trajectory (per-step losses AND final params) is
   BITWISE identical to a fresh run that restores the same checkpoint
   at world=4 on hosts (0,1,2,3) — recovery equals a clean start, down
   to the device identities not mattering; zero steps lost beyond the
   checkpoint cadence; the whole drill deterministic x2.

2. **straggler drill** — three consecutive inflated heartbeats push
   host 2 through slow -> hot -> drain -> shrink; its healthy beats
   after the fault clear probation and the fleet regrows to W=8.
   Gate: exact counters (3 hot steps, 1 drain, 1 shrink, 1 rejoin,
   1 regrow), final world == home world, deterministic x2.

3. **link_flaky drill** — one failed reduce attempt into host 2 is
   absorbed by the in-step retry budget.  Gate: 1 link retry, ZERO
   escalations/drains/shrinks, the run never leaves W=8.

4. **unfired honesty, both directions** — an elastic spec scheduled
   past the end of an ARMED run is counted `faults_unfired` (armed
   but never manifested); the same kinds handed to a plain Injector
   with no elastic harness are flagged by `report_unfired`'s default
   `host_armed=False` (scheduled but nothing was listening).

Run time ~60 s on a laptop CPU, compile-dominated.  No timing asserts,
so a loaded CI runner cannot flake it.

    python tools/bench_elastic.py --smoke     # the CI gate; exit 1 on
                                              # any violation
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _ensure_multidevice():
    """Standalone runs on CPU get the 8-virtual-device platform (the same
    trick as tests/conftest.py) — must happen before jax imports."""
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
    print(f"[elastic-smoke] {tag}: {what}" + (f" ({detail})" if detail
                                              else ""))
    return ok


def _substrate():
    """The shared drill substrate: a tiny CNN under ZeRO-1 SGD — the
    sharded flat momentum makes every shrink/regrow exercise the
    pad_to_world re-flatten, not just a params copy."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cpd_tpu.models import tiny_cnn
    from cpd_tpu.parallel.mesh import make_mesh
    from cpd_tpu.parallel.zero import zero1_sgd
    from cpd_tpu.train import (create_train_state, make_optimizer,
                               make_train_step)

    schedule = lambda s: jnp.float32(0.05)                     # noqa: E731
    model = tiny_cnn()
    tx = make_optimizer("sgd", schedule, momentum=0.9)
    state0 = create_train_state(model, tx,
                                jnp.zeros((2, 32, 32, 3), jnp.float32),
                                jax.random.PRNGKey(0))

    rng = np.random.RandomState(7)
    data_x = rng.randn(64, 32, 32, 3).astype(np.float32)
    data_y = rng.randint(0, 10, size=64).astype(np.int32)

    def next_batch(step, world):
        # a PURE function of (step, world): the post-shrink replay and
        # a fresh run at W' draw identical data — the bitwise
        # contract's data half
        r = np.random.RandomState(1_000_003 * world + step)
        idx = r.randint(0, len(data_y), size=2 * world)
        return (jnp.asarray(data_x[idx]), jnp.asarray(data_y[idx]))

    def build_world(world, hosts):
        z = zero1_sgd(schedule, world=world, momentum=0.9)
        mesh = make_mesh(dp=world,
                         devices=[jax.devices()[h] for h in hosts])
        step = make_train_step(model, None, mesh, donate=False,
                               update_fn=z.update_fn,
                               opt_state_spec=z.state_spec())
        template = state0.replace(opt_state=z.init(state0.params))
        return {"step": step, "template": template,
                "relayout": lambda st: z.mesh_layout(st, mesh)[0]}

    return {"state0": state0, "build_world": build_world,
            "next_batch": next_batch}


def _run_drill(sub, tmp, plan_spec, n_steps, **sup_kw):
    """One run_elastic drill from a fresh W=8 state into `tmp`.  Returns
    (losses-by-step dict, final state, ElasticReport, supervisor)."""
    from cpd_tpu.resilience import FaultPlan, Injector
    from cpd_tpu.resilience.elastic import ElasticSupervisor, run_elastic
    from cpd_tpu.train import CheckpointManager

    plan = FaultPlan.parse(plan_spec)
    sup = ElasticSupervisor(8, **sup_kw)
    b8 = sub["build_world"](8, tuple(range(8)))
    state = b8["relayout"](
        sub["state0"].replace(opt_state=b8["template"].opt_state))
    manager = CheckpointManager(tmp, track_best=False)
    losses = {}
    state, report = run_elastic(
        sub["build_world"], state, sub["next_batch"], n_steps,
        supervisor=sup, manager=manager, plan=plan,
        injector=Injector(plan), ckpt_every=2,
        on_step=lambda it, m: losses.__setitem__(it, float(m["loss"])))
    manager.close()
    return losses, state, report, sup


def drill_host_kill(sub, base_dir) -> bool:
    """Drill 1: host_kill -> shrink 8->4, bitwise vs a fresh run from
    the same checkpoint, deterministic x2."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cpd_tpu.parallel.mesh import make_mesh
    from cpd_tpu.train import CheckpointManager

    ok = True
    rounds = []
    for rnd in range(2):
        tmp = os.path.join(base_dir, f"hk{rnd}")
        losses, state, report, sup = _run_drill(
            sub, tmp, "host_kill@5:3", 10)
        ok &= _check(report.completed and report.final_step == 10,
                     f"round {rnd}: run completed through the kill",
                     f"final_step={report.final_step}")
        ok &= _check(report.world == 4 and sup.active_hosts()
                     == (0, 1, 2, 4),
                     f"round {rnd}: shrunk to W'=4 on hosts (0,1,2,4)",
                     f"world={report.world} hosts={sup.active_hosts()}")
        c = sup.counters
        ok &= _check((c["drains"], c["shrinks"], c["heartbeat_misses"],
                      c["regrows"]) == (1, 1, 1, 0),
                     f"round {rnd}: exact counters "
                     f"(1 drain, 1 shrink, 1 miss, 0 regrows)", str(c))
        # the resume point is the newest SEALED checkpoint (step 4 at
        # cadence 2, killed at 5): zero steps lost beyond the cadence
        resumed = min(t[0] for t in sup.transitions) if sup.transitions \
            else -1
        ok &= _check(resumed == 5 and 4 in losses,
                     f"round {rnd}: transition at step 5, replay from "
                     f"the step-4 seal", f"transitions={sup.transitions}")

        # --- the bitwise contract: fresh run, same checkpoint, W'=4,
        # DIFFERENT devices (0,1,2,3) — device identity must not matter
        b4 = sub["build_world"](4, (0, 1, 2, 3))
        mgr = CheckpointManager(tmp, track_best=False)
        fresh = mgr.restore(b4["template"], step=4, world=4)
        mgr.close()
        ok &= _check(fresh is not None,
                     f"round {rnd}: the step-4 seal restores at W'=4")
        fstate = b4["relayout"](fresh)
        flosses = {}
        it = int(fresh.step)
        while it < 10:
            fstate, m = b4["step"](fstate, *sub["next_batch"](it, 4))
            flosses[it] = float(m["loss"])
            it += 1
        post = {s: l for s, l in losses.items() if s >= 4}
        ok &= _check(post == flosses,
                     f"round {rnd}: post-shrink losses BITWISE == fresh "
                     f"run from the same checkpoint",
                     f"elastic={post} fresh={flosses}")
        ep = jax.tree.leaves(jax.tree.map(np.asarray, state.params))
        fp = jax.tree.leaves(jax.tree.map(np.asarray, fstate.params))
        same = all(np.array_equal(a.view(np.uint32), b.view(np.uint32))
                   for a, b in zip(ep, fp))
        ok &= _check(same, f"round {rnd}: final params BITWISE == fresh "
                           f"run's (across device sets)")
        rounds.append((dict(losses), report.events,
                       dict(sup.counters)))
    ok &= _check(rounds[0] == rounds[1],
                 "drill deterministic x2 (losses, events, counters)")
    return ok


def drill_straggler(sub, base_dir) -> bool:
    """Drill 2: straggler -> hot -> drain -> shrink -> probation ->
    regrow, exact counters, deterministic x2."""
    ok = True
    rounds = []
    spec = "straggler@4:2:4,straggler@5:2:4,straggler@6:2:4"
    for rnd in range(2):
        tmp = os.path.join(base_dir, f"st{rnd}")
        losses, state, report, sup = _run_drill(
            sub, tmp, spec, 14, patience=3, probation=4)
        ok &= _check(report.completed and report.final_step == 14,
                     f"round {rnd}: run completed through the straggler")
        c = sup.counters
        ok &= _check((c["hot_steps"], c["drains"], c["shrinks"],
                      c["rejoins"], c["regrows"]) == (3, 1, 1, 1, 1),
                     f"round {rnd}: exact counters (3 hot, 1 drain, "
                     f"1 shrink, 1 rejoin, 1 regrow)", str(c))
        ok &= _check(report.world == 8 and not sup.degraded,
                     f"round {rnd}: regrown to the home world",
                     f"world={report.world}")
        kinds = [e[0] for e in report.events]
        ok &= _check(kinds.index("elastic_shrink")
                     < kinds.index("elastic_regrow"),
                     f"round {rnd}: shrink precedes regrow in the "
                     f"event log")
        rounds.append((dict(losses), report.events, dict(c)))
    ok &= _check(rounds[0] == rounds[1],
                 "drill deterministic x2 (losses, events, counters)")
    return ok


def drill_link_flaky(sub, base_dir) -> bool:
    """Drill 3: a flaky link absorbed by the in-step retry budget —
    zero escalations, zero shrinks, the world never moves."""
    ok = True
    tmp = os.path.join(base_dir, "lf")
    losses, state, report, sup = _run_drill(
        sub, tmp, "link_flaky@3:2:1", 8)
    ok &= _check(report.completed and report.final_step == 8,
                 "run completed through the flaky link")
    c = sup.counters
    ok &= _check((c["link_retries"], c["link_escalations"],
                  c["drains"], c["shrinks"]) == (1, 0, 0, 0),
                 "exact counters (1 retry, 0 escalations/drains/"
                 "shrinks)", str(c))
    ok &= _check(report.world == 8 and sup.transitions == [],
                 "the world never moved", f"world={report.world}")
    ok &= _check(len(losses) == 8,
                 "all 8 steps trained (the retry cost no step)")
    return ok


def drill_unfired(sub, base_dir) -> bool:
    """Drill 4: unfired-fault honesty, both directions."""
    from cpd_tpu.resilience import FaultPlan, Injector, report_unfired
    from cpd_tpu.train.metrics import ResilienceMeter

    ok = True
    # armed direction: the harness runs, the spec never manifests (it
    # is scheduled past the end) — counted unfired, nothing shrinks
    tmp = os.path.join(base_dir, "uf")
    losses, state, report, sup = _run_drill(
        sub, tmp, "host_kill@50:3", 6)
    ok &= _check(report.counters["faults_unfired"] >= 1
                 and report.world == 8
                 and sup.counters["shrinks"] == 0,
                 "armed + never-fired spec counted faults_unfired, "
                 "world untouched",
                 f"unfired={report.counters['faults_unfired']}")
    # unarmed direction: the same kinds on a plain Injector with no
    # elastic harness listening — report_unfired's default
    # host_armed=False flags all three
    plan = FaultPlan.parse("host_kill@2:1,straggler@3:1:4,"
                           "link_flaky@4:1:2")
    meter = ResilienceMeter()
    report_unfired(Injector(plan), n_steps=10, meter=meter, rank=1)
    ok &= _check(meter["faults_unfired"] == 3,
                 "unarmed run flags every elastic kind as unfired",
                 f"unfired={meter['faults_unfired']}")
    return ok


def run_smoke() -> int:
    import tempfile

    from cpd_tpu.obs.timing import now
    t0 = now()
    sub = _substrate()
    ok = True
    with tempfile.TemporaryDirectory() as base:
        ok &= drill_host_kill(sub, base)
        ok &= drill_straggler(sub, base)
        ok &= drill_link_flaky(sub, base)
        ok &= drill_unfired(sub, base)
    print(json.dumps({"bench": "elastic", "smoke": bool(ok),
                      "secs": round(now() - t0, 1)}))
    return 0 if ok else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--smoke", action="store_true",
                   help="run the elastic-smoke CI gate drills")
    args = p.parse_args(argv)
    if not args.smoke:
        p.error("this tool currently only has --smoke (the CI gate)")
    return run_smoke()


if __name__ == "__main__":
    _ensure_multidevice()
    sys.exit(main())
