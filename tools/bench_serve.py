#!/usr/bin/env python
"""Serving load-generator harness — tok/s, TTFT/TPOT percentiles, goodput.

Replays synthetic arrival traces (Poisson / bursty / mixed) through the
continuous-batching `cpd_tpu.serve.ServeEngine` and reports the serving
metric set into one JSON line (the same schema as bench.py's ``serving``
block): aggregate tok/s, p50/p99 time-to-first-token, p50/p99 per-token
latency, and goodput under an SLA — plus the serial `generate()`
baseline the continuous batch must beat.

``--smoke`` is the CI `serve-smoke` gate (PR 2-5 style: deterministic
counters asserted TWICE, never a timing flake deciding pass/fail except
the explicit speedup gate):

  1. mixed trace on two FRESH engines -> identical counters, zero
     dropped requests, every request completed;
  2. kv_flip fault drill: injected page corruption is detected by the
     page digests and repaired — request completes, counters exact,
     deterministic across two runs;
  3. bitwise gate: the packed (8,23) cache's sampled logits are
     bit-identical to the raw-fp32-cache oracle's;
  4. speedup gate: continuous batching sustains strictly higher
     aggregate tok/s than serial batch-1 `generate()` on the same trace
     (best of two engine passes, after a warmup pass for both sides);
  5. overload drill (ISSUE 10): an SLA-classed flash crowd against a
     bounded queue + tight deadlines -> shed and deadline-miss counters
     nonzero, EXACT and identical across two runs, zero silent drops
     (every submitted rid resolves to FINISHED/SHED/DEADLINE_MISS);
  6. snapshot drill: save mid-trace -> restore -> the remaining decode
     stream is BITWISE identical to the uninterrupted engine at (8,23);
  7. slot-stall watchdog drill: a wedged decode lane is evicted and
     re-prefilled from history by the no-progress watchdog — output
     identical to the stall-free run, counters exact twice.

Drill traces (5-7) are deliberately SHORT (8 requests, max_new 8) so
the gate stays inside its CI time budget; they reuse the compiled step
programs of gates 1-4.

``--overload-sweep`` maps the overload frontier for docs/PERF.md: the
same SLA-classed trace at increasing Poisson offered rates, reporting
offered load vs goodput / shed_rate / deadline_miss_rate.

``--fleet`` maps the FLEET frontier (ISSUE 13) for docs/PERF.md: the
same offered trace behind a `cpd_tpu.fleet.Fleet` at N = 1, 2, 4
engines (tok/s, goodput, shed rate — how admission-pressure sheds melt
as engines are added), plus a prefix-hit-rate sweep on shared-prompt
traces (hit rate, prefill chunks skipped, resident KV bytes saved —
`quant.numerics.kv_pool_bytes` prices the dedup).

``--fleet-smoke`` is the CI `fleet-smoke` gate (N = 2, short traces,
compiled cfgs shared across engines through the serve step cache):

  1. routed mixed trace on two fresh fleets -> identical fleet AND
     per-engine counters, zero fleet-scope silent drops;
  2. live migration drill: one session migrated mid-decode between
     engines -> its remaining decode stream (and every other
     request's) BITWISE identical to the unmigrated fleet run;
  3. engine-kill drill: ``engine_kill`` under chaos -> snapshot+replay
     recovery, drain to the survivor, zero silent drops, counters
     exact and identical across two runs;
  4. prefix-cache drill: shared-prompt trace -> confirmed hits, chunks
     skipped, sampled logits bitwise identical to the cache-less
     fleet, and the crafted Fletcher-collision pair must NOT share.

``--soak-smoke`` is the CI `soak-smoke` gate (ISSUE 17): one
STREAMING soak crossing every elastic-fleet mechanism — generator-fed
arrivals, a mid-run ``kill_wave``, a ``req_burst`` flash crowd,
autoscaler scale-up under the resulting pressure and scale-down
through the idle tail — zero silent drops, bounded per-request RSS
(stores at cap, tracking peaks at in-flight width), fleet/scaler
counters and the full ``shape_log`` exact across two fresh soaks.

Run it by hand for the docs/PERF.md numbers:

    JAX_PLATFORMS=cpu python tools/bench_serve.py --trace mixed \
        --requests 16 --kv-format e5m2
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the sharded drills (--tp-sweep, fleet-smoke gate 5) need a multi-device
# host: force virtual CPU devices BEFORE any jax backend initializes
# (no-op on a real TPU slice, where the platform brings its own devices)
_TP_FLAG = "--xla_force_host_platform_device_count=8"
if _TP_FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = \
        (os.environ.get("XLA_FLAGS", "") + " " + _TP_FLAG).strip()

# the ONE eXmY spec parser (validated, good errors) — not a local copy
from cpd_tpu.resilience.precision import parse_format  # noqa: E402


# The smoke model: big enough that batched decode beats the serial
# fused-scan generate() on a CPU host (measured ~2x at this shape —
# docs/PERF.md "Serving smoke"), small enough to compile in seconds.
_SMOKE_MODEL = dict(vocab_size=512, d_model=256, n_layers=3, n_heads=8,
                    n_kv_heads=2, d_ff=512)
_SMOKE_ENGINE = dict(n_slots=8, max_seq=48, page_size=8, prefill_chunk=8)


def _build_model(args):
    import jax
    import jax.numpy as jnp

    from cpd_tpu.models import transformer_lm

    model = transformer_lm(**_SMOKE_MODEL)
    params = model.init(jax.random.PRNGKey(args.seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _build_trace(args):
    from cpd_tpu.serve import bursty_trace, mixed_trace, poisson_trace

    kw = dict(prompt_lens=(4, 8, 12), max_new=(16,), seed=args.seed)
    vocab = _SMOKE_MODEL["vocab_size"]
    if args.trace == "poisson":
        return poisson_trace(args.requests, vocab, rate=args.rate, **kw)
    if args.trace == "bursty":
        return bursty_trace(args.requests, vocab, burst=4, gap=4, **kw)
    return mixed_trace(args.requests, vocab, **kw)


def _rss_mb() -> float:
    """Current resident set in MB — /proc on Linux, ru_maxrss (a
    high-water mark, still monotone-comparable across rounds) elsewhere."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1]) / 1024.0
    except OSError:
        pass
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _fresh_engine(model, params, args, **over):
    from cpd_tpu.serve import ServeEngine

    kw = dict(_SMOKE_ENGINE, kv_format=args.kv_format, seed=args.seed)
    kw.update(over)
    return ServeEngine(model, params, **kw)


def run_load(args) -> dict:
    from cpd_tpu.serve import run_trace, serial_baseline

    model, params = _build_model(args)
    trace = _build_trace(args)
    # the SHARED obs surface (utils.config): per-request timelines +
    # phase spans + the flight ring on the MEASURED engine, so the
    # exported artifacts describe the run whose numbers this JSON
    # publishes
    from cpd_tpu.utils.config import build_obs
    obs = build_obs(args, run="bench_serve",
                    meta={"trace": args.trace,
                          "kv_format": list(args.kv_format)})
    run_trace(_fresh_engine(model, params, args), list(trace))  # warm
    eng = _fresh_engine(model, params, args, tracer=obs["tracer"],
                        flight=obs["flight"])
    metrics = run_trace(eng, list(trace),
                        sla_ttft_ms=args.sla_ttft_ms,
                        sla_tpot_ms=args.sla_tpot_ms)
    base = serial_baseline(model, params, trace)
    metrics["serial_baseline"] = base
    if base["tok_per_s"]:
        metrics["speedup_vs_serial"] = round(
            metrics["tok_per_s"] / base["tok_per_s"], 2)
    metrics["kv_format"] = list(args.kv_format)
    metrics["trace"] = args.trace
    if obs["active"]:
        from cpd_tpu.serve import timeline_metrics
        obs["registry"].absorb_serve_counters(eng.counters)
        recon = timeline_metrics(obs["tracer"],
                                 sla_ttft_ms=args.sla_ttft_ms,
                                 sla_tpot_ms=args.sla_tpot_ms)
        metrics["obs"] = obs["finish"](ttft_reconstruction_exact=all(
            recon[k] == metrics[k]
            for k in ("ttft_ms_p50", "ttft_ms_p99", "tpot_ms_p50",
                      "tpot_ms_p99", "goodput_tok_per_s")))
    return metrics


def run_smoke(args) -> dict:
    import numpy as np

    from cpd_tpu.resilience import FaultPlan
    from cpd_tpu.serve import run_trace, serial_baseline

    model, params = _build_model(args)
    trace = _build_trace(args)
    out = {"smoke": True, "kv_format": list(args.kv_format),
           "trace": args.trace, "requests": len(trace)}

    # 1. determinism + zero drops: the same mixed trace on two fresh
    # engines must replay to identical counters and finish everything
    run_trace(_fresh_engine(model, params, args), list(trace))  # warm
    m1 = run_trace(_fresh_engine(model, params, args), list(trace))
    m2 = run_trace(_fresh_engine(model, params, args), list(trace))
    assert m1["counters"] == m2["counters"], \
        f"serving counters not deterministic:\n{m1['counters']}\n" \
        f"{m2['counters']}"
    assert m1["dropped"] == 0 and m1["completed"] == len(trace), \
        f"dropped requests: {m1['dropped']}/{len(trace)}"
    out["determinism"] = {"counters_equal": True,
                          "completed": m1["completed"], "dropped": 0}

    # 2. kv_flip drill: corruption detected by the page digest, repaired
    # by recomputation, request still completes — twice, identically
    plan = FaultPlan.parse("kv_flip@6:0")
    e1 = _fresh_engine(model, params, args, scrub_every=2,
                       fault_plan=plan)
    f1 = run_trace(e1, list(trace))
    e2 = _fresh_engine(model, params, args, scrub_every=2,
                       fault_plan=plan)
    f2 = run_trace(e2, list(trace))
    c = f1["counters"]
    assert c == f2["counters"], \
        f"fault-drill counters not deterministic:\n{c}\n{f2['counters']}"
    assert c["kv_flips_injected"] == 1, c
    assert c["kv_pages_corrupt"] >= 1 and c["kv_repairs"] >= 1, c
    assert c["kv_faults_unfired"] == 0, c
    assert f1["dropped"] == 0 and f1["completed"] == len(trace), \
        f"fault drill dropped requests: {f1['dropped']}"
    out["fault_drill"] = {
        "flips_injected": c["kv_flips_injected"],
        "pages_corrupt": c["kv_pages_corrupt"],
        "repairs": c["kv_repairs"], "completed": f1["completed"],
        "deterministic": True}

    # 3. bitwise gate: packed (8,23) logits == raw fp32-cache oracle
    small = list(trace)[:6]
    ea = _fresh_engine(model, params, args, kv_format=(8, 23),
                       record_logits=True)
    eb = _fresh_engine(model, params, args, raw_cache=True,
                       record_logits=True)
    run_trace(ea, list(small))
    run_trace(eb, list(small))
    assert len(ea.logits_log) == len(eb.logits_log) > 0
    for (ra, pa, la), (rb, pb, lb) in zip(ea.logits_log, eb.logits_log):
        assert (ra, pa) == (rb, pb)
        assert (la.view(np.uint32) == lb.view(np.uint32)).all(), \
            f"packed (8,23) logits differ from fp32 oracle at rid={ra} " \
            f"pos={pa}"
    out["bitwise_e8m23_vs_fp32_oracle"] = {"rows": len(ea.logits_log),
                                           "identical": True}

    # 4. speedup gate: aggregate tok/s strictly above serial generate()
    base = serial_baseline(model, params, trace)
    best = max(x for x in (m1["tok_per_s"], m2["tok_per_s"]) if x)
    assert base["tok_per_s"] and best > base["tok_per_s"], \
        f"continuous batching ({best} tok/s) did not beat serial " \
        f"generate ({base['tok_per_s']} tok/s)"
    out["speedup"] = {"engine_tok_per_s": best,
                      "serial_tok_per_s": base["tok_per_s"],
                      "ratio": round(best / base["tok_per_s"], 2)}
    out["metrics"] = {k: m1[k] for k in
                      ("tok_per_s", "ttft_ms_p50", "ttft_ms_p99",
                       "tpot_ms_p50", "tpot_ms_p99",
                       "goodput_tok_per_s")}

    # 5. overload drill (ISSUE 10): SLA-classed burst against a bounded
    # queue + tight class-1 deadlines -> sheds and misses engage, exact
    # and deterministic twice, zero SILENT drops
    from cpd_tpu.serve import with_sla
    drill_trace = with_sla(
        _drill_trace(args),
        [dict(sla_class=0), dict(sla_class=1, deadline_steps=4)])

    def overload_run():
        eng = _fresh_engine(model, params, args, max_queue=2)
        return run_trace(eng, list(drill_trace)), eng

    o1, e1 = overload_run()
    o2, _ = overload_run()
    assert o1["counters"] == o2["counters"], \
        f"overload counters not deterministic:\n{o1['counters']}\n" \
        f"{o2['counters']}"
    assert o1["shed"] + o1["deadline_misses"] > 0, \
        f"overload drill never shed or missed: {o1['counters']}"
    assert o1["dropped"] == 0 and e1.unresolved() == [], \
        f"silent drops under overload: {o1['dropped']} " \
        f"(unresolved {e1.unresolved()})"
    out["overload_drill"] = {
        "submitted": o1["submitted"], "completed": o1["completed"],
        "shed": o1["shed"], "deadline_misses": o1["deadline_misses"],
        "shed_rate": o1["shed_rate"],
        "deadline_miss_rate": o1["deadline_miss_rate"],
        "silent_drops": o1["dropped"], "deterministic": True}

    # 6. snapshot drill: save mid-trace, restore, remaining decode
    # stream bitwise identical at (8,23) (reuses gate 3's compiled cfg;
    # the ONE comparison contract lives in loadgen.decode_tail_matches)
    import tempfile

    from cpd_tpu.serve import ServeEngine, decode_tail_matches

    snap_trace = _drill_trace(args)
    ea = _fresh_engine(model, params, args, kv_format=(8, 23),
                       record_logits=True)
    for r in snap_trace:
        ea.submit(r)
    for _ in range(8):
        ea.step()
    with tempfile.TemporaryDirectory() as td:
        snap = os.path.join(td, "snap")
        ea.snapshot(snap)
        mark = len(ea.logits_log)
        ea.run_until_drained()
        eb = ServeEngine.restore(model, params, snap)
        eb.run_until_drained()
    rows = decode_tail_matches(ea, mark, eb)   # raises on any divergence
    out["snapshot_drill"] = {"rows": rows, "bitwise": True,
                             "restored_at_step": 8}

    # 7. slot-stall watchdog drill: wedged lane evicted + re-prefilled,
    # output identical to the stall-free run, counters exact twice
    stall_plan = FaultPlan.parse("slot_stall@6:0")
    stall_trace = _drill_trace(args)

    def stall_run(plan):
        eng = _fresh_engine(model, params, args, stall_patience=2,
                            fault_plan=plan)
        return run_trace(eng, list(stall_trace)), eng

    s1, se1 = stall_run(stall_plan)
    s2, _ = stall_run(stall_plan)
    sc = s1["counters"]
    assert sc == s2["counters"], \
        f"stall counters not deterministic:\n{sc}\n{s2['counters']}"
    assert sc["slot_stalls_injected"] == 1, sc
    assert sc["watchdog_evictions"] >= 1 and sc["watchdog_chunks"] >= 1, sc
    assert sc["kv_faults_unfired"] == 0, sc
    assert s1["dropped"] == 0 and s1["completed"] == len(stall_trace), sc
    clean, ce = stall_run(None)
    assert ce.finished == se1.finished, \
        "watchdog recovery changed the decoded tokens"
    out["watchdog_drill"] = {
        "stalls": sc["slot_stalls_injected"],
        "evictions": sc["watchdog_evictions"],
        "reprefill_chunks": sc["watchdog_chunks"],
        "completed": s1["completed"],
        "output_matches_stall_free": True, "deterministic": True}

    # 8. blocked-KV gates (ISSUE 12 leg 2): (a) the blocked page codec
    # decodes the blocked cast BITWISE at real page/GQA row shapes
    # (including an odd tail block); (b) a blocked engine replays
    # deterministically with zero drops; (c) the page-corruption-repair
    # drill works under block scaling — the shift sidecar lives in the
    # page, so the digest catches a flip exactly as before and repair
    # recomputes
    import jax.numpy as jnp
    from cpd_tpu.quant.numerics import cast_body_blocked
    from cpd_tpu.serve.kvcache import KVCacheConfig, pack_kv, unpack_kv
    bcfg = KVCacheConfig(n_layers=1,
                         n_kv_heads=_SMOKE_MODEL["n_kv_heads"],
                         head_dim=(_SMOKE_MODEL["d_model"]
                                   // _SMOKE_MODEL["n_heads"]),
                         page_size=8, n_pages=4, exp_bits=4, man_bits=3,
                         block_scale=True, block_size=24)
    rng_b = np.random.RandomState(5)
    kvals = jnp.asarray(
        (rng_b.randn(16, bcfg.n_kv_heads, bcfg.head_dim)
         * np.exp2(rng_b.randint(-18, 12, (16, 1, 1))))
        .astype(np.float32))
    decoded = unpack_kv(pack_kv(kvals, bcfg), bcfg)
    want_b = cast_body_blocked(
        kvals.reshape(16, bcfg.row_elems), 4, 3,
        bcfg.block_size).reshape(16, bcfg.n_kv_heads, bcfg.head_dim)
    assert (np.asarray(decoded).view(np.uint32)
            == np.asarray(want_b).view(np.uint32)).all(), \
        "blocked KV decode != blocked cast (bitwise)"

    bk1 = run_trace(_fresh_engine(model, params, args, kv_format=(4, 3),
                                  kv_block_size=24), list(trace))
    bk2 = run_trace(_fresh_engine(model, params, args, kv_format=(4, 3),
                                  kv_block_size=24), list(trace))
    assert bk1["counters"] == bk2["counters"], \
        f"blocked-KV counters not deterministic:\n{bk1['counters']}\n" \
        f"{bk2['counters']}"
    assert bk1["dropped"] == 0 and bk1["completed"] == len(trace), bk1

    bplan = FaultPlan.parse("kv_flip@6:0")
    bf1 = run_trace(_fresh_engine(model, params, args, kv_format=(4, 3),
                                  kv_block_size=24, scrub_every=2,
                                  fault_plan=bplan), list(trace))
    bf2 = run_trace(_fresh_engine(model, params, args, kv_format=(4, 3),
                                  kv_block_size=24, scrub_every=2,
                                  fault_plan=bplan), list(trace))
    bc = bf1["counters"]
    assert bc == bf2["counters"], \
        f"blocked fault-drill counters not deterministic:\n{bc}"
    assert bc["kv_flips_injected"] == 1, bc
    assert bc["kv_pages_corrupt"] >= 1 and bc["kv_repairs"] >= 1, bc
    assert bf1["dropped"] == 0 and bf1["completed"] == len(trace), bc
    out["blocked_kv"] = {
        "codec_bitwise_vs_blocked_cast": True,
        "deterministic": True, "completed": bk1["completed"],
        "repair_drill": {"flips": bc["kv_flips_injected"],
                         "pages_corrupt": bc["kv_pages_corrupt"],
                         "repairs": bc["kv_repairs"]}}
    return out


def _drill_trace(args) -> list:
    """The SHORT trace the ISSUE 10 drills share (time budget: the
    smoke's main trace keeps its 16x16 shape for the speedup margin;
    the drills only need enough traffic to trip their mechanisms)."""
    from cpd_tpu.serve import mixed_trace

    return mixed_trace(8, _SMOKE_MODEL["vocab_size"],
                       prompt_lens=(4, 8, 12), max_new=(8,),
                       seed=args.seed + 17)


def run_overload_sweep(args) -> dict:
    """The overload frontier for docs/PERF.md: the same SLA-classed
    request population at increasing Poisson offered rates through a
    bounded-queue engine — offered load vs goodput, shed and
    deadline-miss rates.  Class 0 is best-effort, class 1 carries a
    TTFT deadline; past saturation the deadline bound sheds class-1
    work at admission instead of letting everything miss."""
    from cpd_tpu.serve import poisson_trace, run_trace, with_sla

    model, params = _build_model(args)
    rows = []
    for rate in (0.5, 1.0, 2.0, 4.0, 8.0):
        trace = with_sla(
            poisson_trace(args.requests, _SMOKE_MODEL["vocab_size"],
                          rate=rate, prompt_lens=(4, 8, 12),
                          max_new=(16,), seed=args.seed),
            [dict(sla_class=0),
             dict(sla_class=1, deadline_steps=args.deadline_steps)])
        span = max(r.arrival for r in trace) + 1
        run_trace(_fresh_engine(model, params, args, max_queue=4),
                  list(trace))        # warm
        m = run_trace(_fresh_engine(model, params, args, max_queue=4),
                      list(trace))
        rows.append({
            "rate": rate,
            "offered_req_per_step": round(len(trace) / span, 3),
            "tok_per_s": m["tok_per_s"],
            "goodput_tok_per_s": m["goodput_tok_per_s"],
            "goodput_by_class": m["goodput_by_class"],
            "shed_rate": m["shed_rate"],
            "deadline_miss_rate": m["deadline_miss_rate"],
            "completed": m["completed"], "shed": m["shed"],
            "deadline_misses": m["deadline_misses"],
            "dropped": m["dropped"],
        })
    return {"overload_sweep": rows, "requests": args.requests,
            "deadline_steps": args.deadline_steps,
            "kv_format": list(args.kv_format)}


def run_kv_sweep(args) -> dict:
    """The KV-page accuracy-vs-capacity frontier (ISSUE 12 satellite):
    per-tensor vs block-scaled pages per format, scored as max/mean
    absolute logit deviation from the raw fp32-cache oracle over the
    common decode prefix, priced by `kv_page_bytes` (sidecar included).
    The serving twin of bench_reduce's --block-sweep: KV memory is the
    capacity ceiling, so fewer bytes/page at equal accuracy = more
    resident requests per HBM byte."""
    import numpy as np

    from cpd_tpu.quant.numerics import kv_page_bytes
    from cpd_tpu.serve import run_trace

    model, params = _build_model(args)
    trace = _build_trace(args)[:8]
    eo = _fresh_engine(model, params, args, raw_cache=True,
                       record_logits=True)
    run_trace(eo, list(trace))
    hkv = _SMOKE_MODEL["n_kv_heads"]
    hd = _SMOKE_MODEL["d_model"] // _SMOKE_MODEL["n_heads"]
    page = _SMOKE_ENGINE["page_size"]

    rows = []
    for fmt in ((5, 7), (5, 2), (4, 3)):
        for block in (None, 32, 16):
            if block is not None and fmt == (5, 7):
                continue        # the per-tensor baseline format
            eng = _fresh_engine(
                model, params, args, kv_format=fmt,
                kv_block_size=block, record_logits=True)
            run_trace(eng, list(trace))
            err_max = err_mean = 0.0
            n_rows = 0
            for (rn, pn, ln), (ro, po, lo) in zip(eng.logits_log,
                                                  eo.logits_log):
                if (rn, pn) != (ro, po):
                    break       # token divergence re-schedules
                d = np.abs(ln - lo)
                err_max = max(err_max, float(d.max()))
                err_mean += float(d.mean())
                n_rows += 1
            rows.append({
                "format": list(fmt), "block": block,
                "page_bytes": kv_page_bytes(*fmt, page, hkv, hd,
                                            block_size=block),
                "logit_err_max": round(err_max, 4),
                "logit_err_mean": round(err_mean / max(n_rows, 1), 5),
                "rows_compared": n_rows,
                "completed": eng.counters["completed"]})
    fp32_page = 2 * page * hkv * hd * 4
    return {"kv_sweep": rows, "fp32_page_bytes": fp32_page,
            "model": dict(_SMOKE_MODEL), "page_size": page,
            "requests": len(trace)}


def run_tp_sweep(args) -> dict:
    """The sharded serving frontier (ISSUE 18) for docs/PERF.md: the
    same offered trace through tensor-parallel engines at tp = 1, 2, 4
    — aggregate tok/s plus the ANALYTIC per-token cross-shard wire
    (the per-layer quantized all_gather of attention outputs, priced by
    `gather_transport_bytes`, the same ledger the --ir gate pins) —
    and the fused gather→unpack→attention kernel's decode hot-path
    timing vs the XLA composition (fused_attn=True vs False on
    otherwise identical engines).  The tp=4 rows need 4 KV head
    groups, so the sweep model widens _SMOKE_MODEL to n_kv_heads=4."""
    import jax
    import jax.numpy as jnp

    from cpd_tpu.models import transformer_lm
    from cpd_tpu.parallel.ring import gather_transport_bytes
    from cpd_tpu.serve import ServeEngine, run_trace

    tp_model = dict(_SMOKE_MODEL, n_kv_heads=4)
    model = transformer_lm(**tp_model)
    params = model.init(jax.random.PRNGKey(args.seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    trace = _build_trace(args)
    hd = tp_model["d_model"] // tp_model["n_heads"]

    rows = []
    for tp in (1, 2, 4):
        kw = dict(_SMOKE_ENGINE, kv_format=args.kv_format,
                  seed=args.seed, tp=tp)
        run_trace(ServeEngine(model, params, **kw), list(trace))  # warm
        m = run_trace(ServeEngine(model, params, **kw), list(trace))
        h_loc = tp_model["n_heads"] // tp
        wire = 0 if tp == 1 else tp_model["n_layers"] * \
            gather_transport_bytes(h_loc * hd, tp, *args.kv_format,
                                   compressed=True)
        rows.append({"tp": tp, "tok_per_s": m["tok_per_s"],
                     "wire_bytes_per_token": wire,
                     "completed": m["completed"],
                     "dropped": m["dropped"]})

    # fused decode hot path vs the XLA composition: same engine, same
    # trace, fused_attn flipped (CAVEAT printed with the number: off
    # TPU the kernel runs in interpret mode, so only the TPU timing
    # speaks for the Mosaic lowering)
    fused_rows = []
    for fused in (False, True):
        kw = dict(_SMOKE_ENGINE, kv_format=args.kv_format,
                  seed=args.seed, fused_attn=fused)
        run_trace(ServeEngine(model, params, **kw), list(trace))  # warm
        m = run_trace(ServeEngine(model, params, **kw), list(trace))
        fused_rows.append({"fused_attn": fused,
                           "tok_per_s": m["tok_per_s"],
                           "completed": m["completed"]})
    return {"tp_sweep": rows, "fused_hot_path": fused_rows,
            "backend": jax.default_backend(),
            "model": tp_model, "requests": len(trace),
            "kv_format": list(args.kv_format)}


def _fleet(model, params, args, n_engines, **over):
    from cpd_tpu.fleet import Fleet

    kw = dict(_SMOKE_ENGINE, kv_format=args.kv_format, seed=args.seed)
    ekw = over.pop("engine_over", {})
    kw.update(ekw)
    return Fleet(model, params, n_engines, engine_kw=kw, **over)


def run_fleet(args) -> dict:
    """The fleet frontier + prefix-hit-rate sweep for docs/PERF.md
    (module docstring)."""
    from cpd_tpu.quant.numerics import kv_pool_bytes
    from cpd_tpu.serve import shared_prefix_trace
    from cpd_tpu.serve.loadgen import run_fleet_trace

    model, params = _build_model(args)
    # one offered load, growing fleet: the same SLA-classed trace that
    # saturates one engine (bounded queues, class-1 deadlines) is
    # re-offered to N engines — sheds melt, goodput scales
    from cpd_tpu.serve import poisson_trace, with_sla
    trace = with_sla(
        poisson_trace(args.requests * 2, _SMOKE_MODEL["vocab_size"],
                      rate=4.0, prompt_lens=(4, 8, 12), max_new=(16,),
                      seed=args.seed),
        [dict(sla_class=0),
         dict(sla_class=1, deadline_steps=args.deadline_steps)])
    frontier = []
    for n in (1, 2, 4):
        _m = run_fleet_trace(
            _fleet(model, params, args, n,
                   engine_over={"max_queue": 4}), list(trace))  # warm
        m = run_fleet_trace(
            _fleet(model, params, args, n,
                   engine_over={"max_queue": 4}), list(trace))
        frontier.append({
            "n_engines": n,
            "tok_per_s": m["tok_per_s"],
            "goodput_tok_per_s": m["goodput_tok_per_s"],
            "shed_rate": m["shed_rate"],
            "deadline_miss_rate": m["deadline_miss_rate"],
            "completed": m["completed"], "shed": m["shed"],
            "dropped": m["dropped"],
            "router_retries": m["fleet_counters"]["router_retries"],
        })

    # prefix-hit-rate sweep: fewer distinct prefixes = more sharing
    hkv = _SMOKE_MODEL["n_kv_heads"]
    hd = _SMOKE_MODEL["d_model"] // _SMOKE_MODEL["n_heads"]
    page = _SMOKE_ENGINE["page_size"]
    prefix_rows = []
    for n_prefixes in (8, 4, 2, 1):
        sp = shared_prefix_trace(
            args.requests, _SMOKE_MODEL["vocab_size"],
            n_prefixes=n_prefixes, prefix_len=2 * page,
            suffix_lens=(2, 4), max_new=(8,), rate=2.0,
            seed=args.seed)
        fleet = _fleet(model, params, args, 2, prefix_cache_pages=64)
        m = run_fleet_trace(fleet, list(sp))
        agg = fleet.aggregate_counters()
        shared = agg["prefix_pages_shared"]
        pool = kv_pool_bytes(
            *args.kv_format, page, hkv, hd,
            n_layers=_SMOKE_MODEL["n_layers"],
            logical_pages=agg["pages_reserved"], shared_pages=shared)
        prefix_rows.append({
            "n_prefixes": n_prefixes,
            "hit_rate": round(agg["prefix_hits"] / m["submitted"], 3),
            "pages_shared": shared,
            "prefill_chunks": agg["prefill_chunks"],
            "tokens_skipped": agg["prefix_tokens_skipped"],
            "kv_bytes_saved": pool["saved_bytes"],
            "kv_bytes_logical": pool["logical_bytes"],
            "tok_per_s": m["tok_per_s"],
            "dropped": m["dropped"],
        })
    return {"fleet_frontier": frontier, "prefix_sweep": prefix_rows,
            "requests": args.requests, "kv_format": list(args.kv_format),
            "deadline_steps": args.deadline_steps}


def run_fleet_smoke(args) -> dict:
    """The CI `fleet-smoke` gate (module docstring): N=2 drills, short
    traces, deterministic counters asserted twice."""
    import numpy as np

    from cpd_tpu.fleet import PrefixCache, token_digest
    from cpd_tpu.resilience import FaultPlan
    from cpd_tpu.serve import mixed_trace, shared_prefix_trace
    from cpd_tpu.serve.loadgen import run_fleet_trace
    from cpd_tpu.serve.scheduler import DECODE

    model, params = _build_model(args)
    trace = _drill_trace(args)
    out = {"fleet_smoke": True, "kv_format": list(args.kv_format)}

    # 1. routing determinism + fleet-scope zero silent drops
    def route_run():
        fleet = _fleet(model, params, args, 2)
        return run_fleet_trace(fleet, list(trace)), fleet

    r1, f1 = route_run()
    r2, _ = route_run()
    assert r1["fleet_counters"] == r2["fleet_counters"], \
        f"fleet counters not deterministic:\n{r1['fleet_counters']}\n" \
        f"{r2['fleet_counters']}"
    assert r1["engine_counters"] == r2["engine_counters"], \
        "per-engine counters not deterministic"
    assert r1["dropped"] == 0 and f1.unresolved() == [], \
        f"fleet-scope silent drops: {r1['dropped']} " \
        f"(unresolved {f1.unresolved()})"
    assert r1["completed"] == len(trace), r1
    # both engines actually served traffic (the router spread load)
    served = [c["admitted"] for c in r1["engine_counters"]]
    assert all(s > 0 for s in served), \
        f"router left an engine idle: admitted per engine = {served}"
    out["routing"] = {"completed": r1["completed"],
                      "admitted_per_engine": served,
                      "deterministic": True, "silent_drops": 0}

    # 2. live migration mid-decode: bitwise vs the unmigrated fleet run
    def decode_rows(fleet):
        rows = {}
        for e in fleet.engines:
            for rid, pos, row in e.logits_log:
                rows[(rid, pos)] = row
        return rows

    def mig_run(migrate: bool, **extra_over):
        fleet = _fleet(model, params, args, 2,
                       engine_over={"kv_format": (8, 23),
                                    "record_logits": True,
                                    **extra_over})
        pending = sorted(trace, key=lambda r: (r.arrival, r.rid))
        moved = None
        while pending or not fleet.drained():
            while pending and pending[0].arrival <= fleet.step_index:
                fleet.submit(pending.pop(0))
            if migrate and moved is None and fleet.step_index >= 6:
                # first DECODE session in rid order — deterministic
                for rid in sorted(fleet.placement):
                    src = fleet.placement[rid]
                    sl = fleet.engines[src].slot_of_rid(rid)
                    if sl is not None and sl.state == DECODE:
                        fleet.migrate(rid)
                        moved = rid
                        break
            fleet.step()
        return fleet, moved

    base, _ = mig_run(False)
    mig, moved = mig_run(True)
    assert moved is not None, "migration drill never found a live session"
    assert mig.counters["migrations"] == 1
    b_rows, m_rows = decode_rows(base), decode_rows(mig)
    assert b_rows.keys() == m_rows.keys() and len(b_rows) > 0
    for key in b_rows:
        assert (b_rows[key].view(np.uint32)
                == m_rows[key].view(np.uint32)).all(), \
            f"migrated fleet logits differ from unmigrated at {key}"
    assert mig.unresolved() == []
    out["migration"] = {"migrated_rid": moved,
                        "rows_compared": len(b_rows), "bitwise": True}

    # 3. engine-kill drill: snapshot+replay recovery, drain, exact x2
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        def kill_run(sub):
            plan = FaultPlan.parse("engine_kill@6:1")
            fleet = _fleet(model, params, args, 2, fault_plan=plan,
                           snapshot_every=4,
                           snapshot_dir=os.path.join(td, sub))
            m = run_fleet_trace(fleet, list(trace))
            return m, fleet

        k1, kf1 = kill_run("a")
        k2, _ = kill_run("b")
    assert k1["fleet_counters"] == k2["fleet_counters"], \
        f"kill-drill counters not deterministic:\n{k1['fleet_counters']}" \
        f"\n{k2['fleet_counters']}"
    assert k1["engine_counters"] == k2["engine_counters"]
    assert k1["fleet_counters"]["engine_kills"] == 1
    assert k1["fleet_counters"]["drains"] == 1
    assert k1["dropped"] == 0 and kf1.unresolved() == [], \
        f"silent drops after engine kill: {k1['dropped']}"
    assert kf1.report_unfired() == []
    out["engine_kill"] = {
        "kills": k1["fleet_counters"]["engine_kills"],
        "sessions_recovered":
            k1["fleet_counters"]["sessions_recovered"],
        "requeued": k1["fleet_counters"]["requeued"],
        "migrated_out": k1["fleet_counters"]["migrations"],
        "completed": k1["completed"], "silent_drops": 0,
        "deterministic": True}

    # 4. prefix-cache drill: hits engage, chunks skipped, bitwise vs
    # the cache-less fleet; crafted Fletcher collision must not share
    sp = shared_prefix_trace(8, _SMOKE_MODEL["vocab_size"],
                             n_prefixes=2,
                             prefix_len=2 * _SMOKE_ENGINE["page_size"],
                             suffix_lens=(2, 4), max_new=(8,),
                             seed=args.seed + 29)

    def prefix_run(cached):
        fleet = _fleet(model, params, args, 2,
                       engine_over={"record_logits": True},
                       **({"prefix_cache_pages": 64} if cached else {}))
        m = run_fleet_trace(fleet, list(sp))
        return fleet, m

    pc, mc = prefix_run(True)
    pn, mn = prefix_run(False)
    agg = pc.aggregate_counters()
    aggn = pn.aggregate_counters()
    assert agg["prefix_hits"] > 0, agg
    assert agg["prefill_chunks"] < aggn["prefill_chunks"], \
        f"prefix hits skipped no chunks: {agg['prefill_chunks']} vs " \
        f"{aggn['prefill_chunks']}"
    c_rows, n_rows = decode_rows(pc), decode_rows(pn)
    assert c_rows.keys() == n_rows.keys() and len(c_rows) > 0
    for key in c_rows:
        assert (c_rows[key].view(np.uint32)
                == n_rows[key].view(np.uint32)).all(), \
            f"prefix-hit logits differ from cold prefill at {key}"
    assert mc["dropped"] == mn["dropped"] == 0
    # the collision-confirmation rule, on the crafted pair: the
    # position-weighted Fletcher gives (5,9,5) and (6,7,6) the SAME
    # digest, and the byte comparison must refuse the share
    cache = PrefixCache(4)
    a, b = (5, 9, 5), (6, 7, 6)
    assert token_digest(a) == token_digest(b)
    cache.register(a, page_id=3)
    assert cache.lookup(b + (1,), 3) == [], \
        "Fletcher collision shared a page across different prefixes"
    assert cache.lookup(a + (1,), 3) == [3]
    assert cache.collisions_rejected >= 1
    out["prefix_cache"] = {
        "hits": agg["prefix_hits"],
        "pages_shared": agg["prefix_pages_shared"],
        "chunks": [agg["prefill_chunks"], aggn["prefill_chunks"]],
        "rows_compared": len(c_rows), "bitwise": True,
        "collision_rejected": True}

    # 5. tp=2 sharded drill (ISSUE 18): the fleet's engines run
    # tensor-parallel over 2 head groups — routing stays exact x2,
    # a session migrated mid-decode between SHARDED engines resumes
    # bitwise, and a kv_flip on the sharded pool is caught by the
    # per-shard page digests and repaired, deterministically
    from cpd_tpu.serve import run_trace

    def tp_route_run():
        fleet = _fleet(model, params, args, 2, engine_over={"tp": 2})
        return run_fleet_trace(fleet, list(trace)), fleet

    t1, tf1 = tp_route_run()
    t2, _ = tp_route_run()
    assert t1["fleet_counters"] == t2["fleet_counters"], \
        f"tp=2 fleet counters not deterministic:\n{t1['fleet_counters']}" \
        f"\n{t2['fleet_counters']}"
    assert t1["engine_counters"] == t2["engine_counters"], \
        "tp=2 per-engine counters not deterministic"
    assert t1["dropped"] == 0 and tf1.unresolved() == [], \
        f"tp=2 fleet silent drops: {t1['dropped']}"
    assert t1["completed"] == len(trace), t1

    tbase, _ = mig_run(False, tp=2)
    tmig, tmoved = mig_run(True, tp=2)
    assert tmoved is not None, "tp=2 migration drill found no session"
    assert tmig.counters["migrations"] == 1
    tb_rows, tm_rows = decode_rows(tbase), decode_rows(tmig)
    assert tb_rows.keys() == tm_rows.keys() and len(tb_rows) > 0
    for key in tb_rows:
        assert (tb_rows[key].view(np.uint32)
                == tm_rows[key].view(np.uint32)).all(), \
            f"tp=2 migrated fleet logits differ at {key}"
    assert tmig.unresolved() == []

    tplan = FaultPlan.parse("kv_flip@6:0")
    tf_a = run_trace(_fresh_engine(model, params, args, tp=2,
                                   scrub_every=2, fault_plan=tplan),
                     list(trace))
    tf_b = run_trace(_fresh_engine(model, params, args, tp=2,
                                   scrub_every=2, fault_plan=tplan),
                     list(trace))
    tc = tf_a["counters"]
    assert tc == tf_b["counters"], \
        f"tp=2 fault-drill counters not deterministic:\n{tc}"
    assert tc["kv_flips_injected"] == 1, tc
    assert tc["kv_pages_corrupt"] >= 1 and tc["kv_repairs"] >= 1, tc
    assert tf_a["dropped"] == 0 and tf_a["completed"] == len(trace), tc
    out["tp2_drill"] = {
        "routing_deterministic": True, "completed": t1["completed"],
        "migrated_rid": tmoved, "rows_compared": len(tb_rows),
        "migration_bitwise": True,
        "repair": {"flips": tc["kv_flips_injected"],
                   "pages_corrupt": tc["kv_pages_corrupt"],
                   "repairs": tc["kv_repairs"]}}
    return out


def run_soak_smoke(args) -> dict:
    """The CI `soak-smoke` gate (ISSUE 17): ONE streaming soak that
    crosses every elastic-fleet mechanism at once — generator-fed
    arrivals (never materialized as a list), a mid-run ``kill_wave``, a
    ``req_burst`` flash crowd, autoscaler scale-up under the resulting
    pressure and scale-down through the idle tail — asserted exactly
    TWICE:

      1. zero fleet-scope silent drops and an empty unresolved()/
         report_unfired() after the full soak;
      2. the autoscaler actually moved BOTH directions (ups >= 1,
         downs >= 1) and the wave actually fired (kill_waves == 1);
      3. bounded RSS: the per-request streaming state peaks far below
         the session count (stays-at-cap: the bounded stores evicted,
         yet counter-derived resolution stays exact);
      4. determinism x2: fleet counters, scaler counters, the
         shape_log (every spawn/kill/retire decision) and every
         window's COUNT fields identical across two fresh soaks —
         wall-clock percentiles are reported, never gated.

    ``--rounds N`` (ISSUE 19 satellite, the hours-equivalent soak —
    slow tier, recorded in docs/PERF.md) repeats the full x2 soak N
    times with shifted arrival seeds, a fresh fleet each round, and
    additionally gates PROCESS RSS: round 1 pays the jit/compile-cache
    warmup, after which later rounds must hold resident memory flat —
    the leak class a short soak cannot see (accumulating per-round
    state: result stores, shape logs, trace buffers, orbax handles).
    """
    from cpd_tpu.fleet import Autoscaler, AutoscalePolicy
    from cpd_tpu.resilience import FaultPlan
    from cpd_tpu.serve.loadgen import (flash_crowd, run_fleet_trace,
                                       steady_stream)

    model, params = _build_model(args)
    vocab = _SMOKE_MODEL["vocab_size"]
    n_req = 48

    def soak(sub, td, seed):
        policy = AutoscalePolicy(min_engines=1, max_engines=3,
                                 up_page_util=0.55, up_queue=2,
                                 up_patience=2, down_page_util=0.25,
                                 down_patience=6, cooldown_steps=8)
        fleet = _fleet(
            model, params, args, 1,
            engine_over={"finished_cap": 16},
            fault_plan=FaultPlan.parse("kill_wave@20:1"),
            engine_plans=[FaultPlan.parse("req_burst@14:6")],
            snapshot_every=4, snapshot_dir=os.path.join(td, sub),
            autoscaler=Autoscaler(policy))
        gen = steady_stream(n_req, vocab, rate=1.5, prompt_lens=(4, 8),
                            max_new=(6, 8), seed=seed + 17,
                            sla=[{"sla_class": 0}, {"sla_class": 1}])
        res = run_fleet_trace(
            fleet, gen, window_steps=16, min_steps=110,
            burst_factory=flash_crowd(vocab, seed=seed + 31))
        return res, fleet

    import tempfile

    rounds = max(int(getattr(args, "rounds", 1) or 1), 1)
    rss_mb = []
    for rnd in range(rounds):
        seed = args.seed + 1000 * rnd
        with tempfile.TemporaryDirectory() as td:
            r1, f1 = soak("a", td, seed)
            r2, f2 = soak("b", td, seed)

        # 1. nothing dropped, nothing unresolved, every fault consumed
        assert r1["dropped"] == 0 and f1.unresolved() == [], \
            f"soak silent drops: {r1['dropped']} " \
            f"(unresolved {f1.unresolved()})"
        assert f1.report_unfired() == [], \
            f"soak left faults unfired: {f1.report_unfired()}"
        assert r1["submitted"] == n_req + 6, r1["submitted"]  # +burst

        # 2. the fleet actually breathed, and the wave actually hit
        sc = f1.autoscaler.counters
        assert sc["ups"] >= 1 and sc["downs"] >= 1, \
            f"autoscaler never moved both directions: {sc}"
        fc = r1["fleet_counters"]
        assert fc["kill_waves"] == 1 and fc["engines_spawned"] >= 1 \
            and fc["engines_retired"] >= 1, fc
        assert sum(f1.accepting) == 1, \
            f"idle tail should scale back to the floor: " \
            f"{sum(f1.accepting)} accepting"

        # 3. bounded streaming state: stores at cap, tracking at
        # in-flight width — yet counter-derived resolution stays exact
        agg = f1.aggregate_counters()
        assert agg["results_evicted"] > 0, \
            "soak never put the bounded stores at cap — not a soak"
        st = r1["stream"]
        assert st["final_tracked_rids"] == 0
        assert st["peak_tracked_rids"] < r1["submitted"] // 2, \
            f"per-request state not bounded by in-flight width: peak " \
            f"{st['peak_tracked_rids']} of {r1['submitted']} submitted"

        # 4. determinism x2 — counters, decisions, window counts
        assert r1["fleet_counters"] == r2["fleet_counters"], \
            f"soak fleet counters not deterministic:\n" \
            f"{r1['fleet_counters']}\n{r2['fleet_counters']}"
        assert f1.autoscaler.counters == f2.autoscaler.counters, \
            "autoscaler decisions not deterministic"
        assert list(f1.shape_log) == list(f2.shape_log), \
            f"fleet shape history not deterministic:\n" \
            f"{list(f1.shape_log)}\n{list(f2.shape_log)}"
        count_keys = ("start_step", "end_step", "submitted", "completed",
                      "shed", "deadline_misses", "tokens")
        w1 = [{k: w[k] for k in count_keys} for w in r1["windows"]]
        w2 = [{k: w[k] for k in count_keys} for w in r2["windows"]]
        assert w1 == w2, "window count fields not deterministic"

        rss_mb.append(round(_rss_mb(), 1))
        if rounds > 1:
            print(f"[soak] round {rnd + 1}/{rounds} ok, "
                  f"rss {rss_mb[-1]:.0f} MB", flush=True)

    # 5. (--rounds only) hours-equivalent leak gate: once round 1 has
    # paid the jit warmup, resident memory must plateau — per-round
    # growth means some store survives its fleet (ISSUE 19 satellite)
    if rounds > 1:
        grown = rss_mb[-1] - rss_mb[0]
        allowed = max(0.3 * rss_mb[0], 200.0)
        assert grown <= allowed, \
            f"soak RSS grew {grown:.0f} MB over {rounds} rounds " \
            f"({rss_mb} MB) — per-round state is leaking"

    return {"soak_smoke": True, "rounds": rounds, "rss_mb": rss_mb,
            "kv_format": list(args.kv_format),
            "submitted": r1["submitted"], "completed": r1["completed"],
            "shed": r1["shed"],
            "deadline_misses": r1["deadline_misses"],
            "silent_drops": 0, "fleet_steps": r1["fleet_steps"],
            "windows": len(r1["windows"]),
            "peak_tracked_rids": st["peak_tracked_rids"],
            "results_evicted": agg["results_evicted"],
            "scaler": dict(sc), "shape_log": [list(x) for x
                                              in f1.shape_log],
            "deterministic": True}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    p.add_argument("--smoke", action="store_true",
                   help="CI gate: determinism x2, fault drill, bitwise "
                        "oracle, speedup-vs-serial, overload/snapshot/"
                        "watchdog drills")
    p.add_argument("--kv-sweep", action="store_true",
                   help="KV-page accuracy-vs-capacity frontier: "
                        "per-tensor vs block-scaled pages per format "
                        "(ISSUE 12) for docs/PERF.md")
    p.add_argument("--overload-sweep", action="store_true",
                   help="map the overload frontier (offered load vs "
                        "goodput/shed/miss) for docs/PERF.md")
    p.add_argument("--fleet", action="store_true",
                   help="fleet frontier (N=1,2,4 goodput/shed scaling)"
                        " + prefix-hit-rate sweep (ISSUE 13) for "
                        "docs/PERF.md")
    p.add_argument("--fleet-smoke", action="store_true",
                   help="CI gate: N=2 route/migrate/kill/prefix drills"
                        " — bitwise resume, zero silent drops, "
                        "counters exact x2")
    p.add_argument("--tp-sweep", action="store_true",
                   help="sharded serving frontier (ISSUE 18): tok/s + "
                        "per-token cross-shard wire bytes at tp=1,2,4 "
                        "and fused-vs-XLA decode hot path, for "
                        "docs/PERF.md")
    p.add_argument("--soak-smoke", action="store_true",
                   help="CI gate (ISSUE 17): streaming arrivals x "
                        "kill wave x flash crowd x autoscale up/down "
                        "in one soak — zero drops, bounded RSS, "
                        "counters and shape_log exact x2")
    p.add_argument("--deadline-steps", type=int, default=12,
                   help="class-1 TTFT deadline for --overload-sweep")
    p.add_argument("--trace", choices=("poisson", "bursty", "mixed"),
                   default="mixed")
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--rate", type=float, default=2.0,
                   help="poisson arrivals per engine step")
    p.add_argument("--kv-format", type=parse_format, default=(5, 2),
                   help="KV-cache eXmY format (default e5m2)")
    p.add_argument("--sla-ttft-ms", type=float, default=1000.0)
    p.add_argument("--sla-tpot-ms", type=float, default=250.0)
    p.add_argument("--rounds", type=int, default=1,
                   help="repeat the --soak-smoke x2 soak N times "
                        "(fresh fleet, shifted seeds) and gate process "
                        "RSS flat after the round-1 warmup — the "
                        "hours-equivalent leak check (slow tier; "
                        "docs/PERF.md)")
    p.add_argument("--seed", type=int, default=0)
    # the shared --obs-dir/--obs-flight surface (the measured-run
    # artifact bundle; docs/OBSERVABILITY.md)
    from cpd_tpu.utils.config import add_obs_flags
    add_obs_flags(p)
    args = p.parse_args()

    from cpd_tpu.utils import enable_compile_cache
    enable_compile_cache()

    if args.smoke:
        out = run_smoke(args)
    elif args.soak_smoke:
        out = run_soak_smoke(args)
    elif args.fleet_smoke:
        out = run_fleet_smoke(args)
    elif args.tp_sweep:
        out = run_tp_sweep(args)
    elif args.fleet:
        out = run_fleet(args)
    elif args.kv_sweep:
        out = run_kv_sweep(args)
    elif args.overload_sweep:
        out = run_overload_sweep(args)
    else:
        out = run_load(args)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
