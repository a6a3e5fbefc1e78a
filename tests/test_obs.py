"""cpd_tpu.obs — tracing, registry, exporters, flight recorder (ISSUE
11), plus the satellites: the StepProfiler leak fix, the one-timer
dedupe, exporter determinism, and the provably-free contract.

The two acceptance pins:

* **obs is free**: a serve trace and a guarded train loop produce
  BITWISE-identical outputs (finished stores / counters / state) with
  and without a tracer attached — obs only observes;
* **timeline reconstruction is exact**: `loadgen.timeline_metrics` over
  a traced run's per-request timeline reproduces `run_trace`'s
  published TTFT/TPOT percentiles, goodput and counts float-for-float.
"""

import json
import os
import time

import numpy as np
import pytest

from cpd_tpu.obs import (FlightRecorder, MetricsRegistry, NULL_TRACER,
                         Stopwatch, Tracer, export_chrome_trace,
                         export_jsonl, export_prometheus,
                         parse_prometheus, write_all)
from cpd_tpu.obs.timing import Timer, now


# --------------------------------------------------------------- timing

def test_timer_is_the_one_implementation():
    """Satellite: train.metrics.Timer IS obs.timing.Timer (one home)."""
    from cpd_tpu.train.metrics import Timer as TrainTimer
    assert TrainTimer is Timer


def test_timer_accumulates():
    t = Timer()
    a = t()
    b = t(include_in_total=False)
    c = t()
    assert a >= 0 and b >= 0 and c >= 0
    assert t.total_time == pytest.approx(a + c, abs=1e-9)


def test_timer_state_is_o1():
    """Regression (host-unbounded, v4): Timer must keep only the last
    mark — the reference appended every timestamp to a list, which on a
    long-lived loop grows on the step clock forever."""
    t = Timer()
    deltas = [t() for _ in range(50)]
    assert all(d >= 0 for d in deltas)
    assert not any(isinstance(v, (list, dict, set))
                   for v in vars(t).values())


def test_stopwatch_laps_and_elapsed():
    w = Stopwatch()
    d1 = w.lap()
    d2 = w.lap()
    assert d1 >= 0 and d2 >= 0
    assert w.elapsed() >= d1 + d2 - 1e-9


# -------------------------------------------------- StepProfiler (leak fix)

class _FakeProfiler:
    def __init__(self):
        self.running = False
        self.starts = 0
        self.stops = 0

    def start_trace(self, d):
        if self.running:
            raise RuntimeError("trace already running")
        self.running = True
        self.starts += 1

    def stop_trace(self):
        if not self.running:
            raise RuntimeError("no trace running")
        self.running = False
        self.stops += 1


@pytest.fixture
def fake_profiler(monkeypatch, tmp_path):
    import jax
    fake = _FakeProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    return fake


def test_profiler_close_stops_inflight_trace(fake_profiler, tmp_path):
    """Satellite regression: a loop that exits INSIDE the window
    (watchdog interrupt, rollback past the end) must not leak a running
    jax.profiler trace."""
    from cpd_tpu.utils.profiling import StepProfiler
    p = StepProfiler(str(tmp_path / "prof"), start=2, num_steps=3)
    p.step(1)
    p.step(2)                      # window opens
    assert fake_profiler.running
    p.close()                      # loop died inside the window
    assert not fake_profiler.running
    p.close()                      # idempotent
    assert fake_profiler.stops == 1


def test_profiler_rollback_replay_does_not_double_start(fake_profiler,
                                                        tmp_path):
    """A rollback that rewinds the step counter back across the window
    start must not call start_trace on a running (or completed) trace —
    jax.profiler raises on the double start."""
    from cpd_tpu.utils.profiling import StepProfiler
    p = StepProfiler(str(tmp_path / "prof"), start=2, num_steps=3)
    p.step(2)
    p.step(3)
    p.step(2)                      # rollback replay through the window
    assert fake_profiler.starts == 1
    p.step(5)                      # window closes normally
    assert not fake_profiler.running
    p.step(2)                      # second replay after completion
    assert fake_profiler.starts == 1
    p.close()
    assert fake_profiler.stops == 1


# ------------------------------------------------------------------ tracer

def test_spans_nest_and_events_record_steps():
    tr = Tracer("t")
    with tr.span("outer", step=3):
        with tr.span("inner", step=3, cat="serve"):
            pass
        tr.event("mark", step=3, detail=7)
    spans = sorted(tr.spans)
    # inner exits first -> records first
    assert [s[1] for s in spans] == ["inner", "outer"]
    assert spans[0][6] == 1 and spans[1][6] == 0       # depths
    assert spans[0][3] == 3
    (_seq, name, cat, step, _wall, args), = list(tr.events)
    assert (name, cat, step, args) == ("mark", "mark", 3, {"detail": 7})


def test_tracer_ring_is_bounded_and_counts_drops():
    tr = Tracer("t", max_records=4)
    for i in range(10):
        tr.event("e", step=i)
    assert len(tr.events) == 4
    assert tr.events_dropped == 6
    assert [e[3] for e in tr.events] == [6, 7, 8, 9]   # newest kept


def test_annotating_tracer_puts_spans_on_the_profilers_clock(monkeypatch):
    """`Tracer(annotate=True)`: every span also enters a
    `jax.profiler.TraceAnnotation` of its name, properly nested and closed
    on an exception; the records are what they are without it, and the
    plain tracer never touches jax.profiler."""
    import jax.profiler
    log = []

    class FakeAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name, exc[0]))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    plain, noted = Tracer("t"), Tracer("t", annotate=True)
    for tr in (plain, noted):
        with tr.span("step", step=1):
            with tr.span("data", step=1):
                pass
        with pytest.raises(KeyError):
            with tr.span("checkpoint", step=2):
                raise KeyError("x")
    assert log == [("enter", "step"), ("enter", "data"),
                   ("exit", "data", None), ("exit", "step", None),
                   ("enter", "checkpoint"), ("exit", "checkpoint", KeyError)]
    strip = lambda t: [(s[1], s[2], s[3], s[6], s[7]) for s in t.spans]
    assert strip(plain) == strip(noted)
    # the CLI wiring: --obs-dir and --profile-dir together turn it on
    from cpd_tpu.utils.config import build_obs
    on = build_obs(SimpleNamespace(obs_dir="o", profile_dir="p"), run="r")
    off = build_obs(SimpleNamespace(obs_dir="o", profile_dir=None), run="r")
    assert on["tracer"]._annotation is FakeAnnotation
    assert off["tracer"]._annotation is None


def test_null_tracer_is_inert():
    with NULL_TRACER.span("x", step=1):
        NULL_TRACER.event("y")
        NULL_TRACER.request_event(1, "z", 0)
    assert not NULL_TRACER
    assert NULL_TRACER.summary()["spans"] == 0


# ---------------------------------------------------------------- registry

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.inc("cpd_x_total", 2, kind="a")
    reg.inc("cpd_x_total", 3, kind="a")
    reg.inc("cpd_x_total", 1, kind="b")
    reg.set_gauge("cpd_y", 4.5)
    reg.declare("cpd_h", "histogram", buckets=(0.1, 1.0))
    reg.observe("cpd_h", 0.05)
    reg.observe("cpd_h", 0.5)
    reg.observe("cpd_h", 5.0)
    d = reg.as_dict()
    assert d["cpd_x_total"]["value"] == {"kind=a": 5.0, "kind=b": 1.0}
    assert d["cpd_y"]["value"] == 4.5
    h = [r for n, k, _h, _b, r in reg.collect() if n == "cpd_h"][0]
    assert h[0][1] == {"buckets": [1, 1], "sum": 5.55, "count": 3}


def test_registry_one_home_one_name():
    reg = MetricsRegistry()
    reg.inc("cpd_n")
    with pytest.raises(ValueError, match="one home, one name"):
        reg.set_gauge("cpd_n", 1.0)
    with pytest.raises(ValueError):
        reg.inc("cpd_n", -1)
    with pytest.raises(ValueError):
        reg.declare("0bad", "gauge")


def test_registry_absorbs_resilience_meter_and_step_metrics():
    from cpd_tpu.train.metrics import ResilienceMeter
    m = ResilienceMeter()
    m.bump("rollbacks", 2)
    m.observe_metrics({"guard_skipped": 3.0})
    reg = MetricsRegistry()
    reg.absorb_resilience_meter(m)
    d = reg.as_dict()
    assert d["cpd_train_rollbacks"]["value"] == 2.0
    assert d["cpd_train_steps_skipped"]["value"] == 3.0
    # step families adopted, training metrics (loss) left to
    # ScalarWriter
    reg.absorb_step_metrics({"prec_wire_sat": 7.0, "reduce_ok": 1.0,
                             "loss": 0.5, "accuracy": 0.9}, step=11)
    d = reg.as_dict()
    assert d["cpd_step_prec_wire_sat"]["value"] == 7.0
    assert d["cpd_step_reduce_ok"]["value"] == 1.0
    assert d["cpd_step_index"]["value"] == 11.0
    assert "cpd_step_loss" not in d


def test_registry_absorbs_supervisor_state():
    reg = MetricsRegistry()
    reg.absorb_supervisor("precision", {
        "level": 1, "hot": 2, "quiet": 0,
        "site": "wire", "ladder": [[5, 2], [5, 7]],
        "transitions": [[3, "e5m2", "e5m7"]]})
    d = reg.as_dict()
    assert d["cpd_sup_precision_level"]["value"] == 1.0
    assert d["cpd_sup_precision_ladder_len"]["value"] == 2.0
    assert d["cpd_sup_precision_info"]["value"] == {"site=wire": 1.0}


# --------------------------------------------------------------- exporters

def _toy_tracer_and_registry(wall_offset=0.0):
    tr = Tracer("toy", meta={"seed": 1})
    for i in range(3):
        with tr.span("step", step=i, cat="phase"):
            tr.request_event(i, "submit", i, verdict="ACCEPT",
                             arrival=i)
    reg = MetricsRegistry()
    reg.declare("cpd_demo_total", "counter", "demo counter")
    reg.inc("cpd_demo_total", 4, mode="ring")
    reg.set_gauge("cpd_demo_gauge", 1.25)
    reg.declare("cpd_demo_hist", "histogram", buckets=(0.5, 1.5))
    reg.observe("cpd_demo_hist", 1.0)
    return tr, reg


def test_exporters_deterministic_modulo_wall(tmp_path):
    """Satellite: the same logical run exported twice (different wall
    clocks) is byte-identical under strip_wall for BOTH the JSONL and
    the Chrome trace."""
    files = []
    for run in ("a", "b"):
        tr, reg = _toy_tracer_and_registry()
        time.sleep(0.01)   # guarantee the wall clocks differ
        j = export_jsonl(tr, str(tmp_path / f"{run}.jsonl"),
                         strip_wall=True)
        c = export_chrome_trace(tr, str(tmp_path / f"{run}.json"),
                                strip_wall=True)
        files.append((open(j, "rb").read(), open(c, "rb").read()))
    assert files[0][0] == files[1][0]
    assert files[0][1] == files[1][1]
    # and WITH wall the streams still parse per line
    tr, _ = _toy_tracer_and_registry()
    j = export_jsonl(tr, str(tmp_path / "wall.jsonl"))
    for line in open(j):
        rec = json.loads(line)
        assert rec["t"] in ("meta", "span", "event")


def test_chrome_trace_is_wellformed(tmp_path):
    tr, _ = _toy_tracer_and_registry()
    path = export_chrome_trace(tr, str(tmp_path / "t.json"))
    doc = json.load(open(path))
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("M", "X", "i")
        assert "name" in ev and "pid" in ev and "tid" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and "ts" in ev
    # request events ride their rid's lane
    req = [e for e in doc["traceEvents"] if e.get("cat") == "req"]
    assert {e["tid"] for e in req} == {1, 2, 3}


def test_prometheus_roundtrip_and_checker(tmp_path):
    _tr, reg = _toy_tracer_and_registry()
    text = export_prometheus(reg, str(tmp_path / "m.prom"))
    parsed = parse_prometheus(text)
    assert parsed["cpd_demo_total"]["type"] == "counter"
    assert parsed["cpd_demo_total"]["samples"] == [({"mode": "ring"},
                                                    4.0)]
    hist = parsed["cpd_demo_hist"]["samples"]
    les = [s[0].get("le") for s in hist if "le" in s[0]]
    assert les == ["0.5", "1.5", "+Inf"]
    # non-finite values export under the spec spellings instead of
    # crashing the end-of-run artifact write (a diverged run's NaN
    # telemetry), and round-trip through the checker
    reg2 = MetricsRegistry()
    reg2.set_gauge("cpd_bad", float("nan"))
    reg2.set_gauge("cpd_hi", float("inf"), side="up")
    reg2.set_gauge("cpd_lo", float("-inf"))
    text2 = export_prometheus(reg2)
    assert "cpd_bad NaN" in text2 and 'cpd_hi{side="up"} +Inf' in text2
    parsed2 = parse_prometheus(text2)
    assert parsed2["cpd_hi"]["samples"][0][1] == float("inf")
    assert parsed2["cpd_lo"]["samples"][0][1] == float("-inf")
    assert np.isnan(parsed2["cpd_bad"]["samples"][0][1])
    # the minimal checker is a real checker
    with pytest.raises(ValueError, match="malformed sample"):
        parse_prometheus("# TYPE cpd_ok gauge\ncpd_ok 1\n"
                         "not a sample !!\n")
    with pytest.raises(ValueError, match="no preceding # TYPE"):
        parse_prometheus("cpd_untyped 1\n")


def test_write_all_bundle(tmp_path):
    tr, reg = _toy_tracer_and_registry()
    out = write_all(str(tmp_path / "obs"), tr, reg)
    for key, p in out["artifacts"].items():
        assert os.path.isfile(p), key
    assert out["summary"]["spans"] == 3
    assert out["summary"]["metrics"] == 3
    parse_prometheus(open(out["artifacts"]["prometheus"]).read())
    json.load(open(out["artifacts"]["chrome_trace"]))


# ---------------------------------------------------------- flight recorder

def test_flight_ring_bounded_and_dump_appends(tmp_path):
    path = str(tmp_path / "flight.jsonl")
    fr = FlightRecorder(path, capacity=4)
    for i in range(10):
        fr.record("step", step=i, loss=float(i))
    assert len(fr) == 4
    fr.dump("rollback")
    fr.record("step", step=10)
    fr.dump("watchdog")
    lines = [json.loads(ln) for ln in open(path)]
    headers = [ln for ln in lines if "flight_dump" in ln]
    assert [h["reason"] for h in headers] == ["rollback", "watchdog"]
    assert headers[0]["events"] == 4
    # the ring is not cleared by a dump: the second block holds the
    # newest 4 events ending at step 10
    second = lines[len(headers[0:1]) + headers[0]["events"] + 1:]
    assert second[-1]["step"] == 10


def test_flight_without_path_is_loud_but_safe(capsys):
    fr = FlightRecorder(None, capacity=2)
    fr.record("step", step=1)
    assert fr.dump("watchdog") is None
    assert "no dump path" in capsys.readouterr().err


def test_watchdog_on_trip_dumps_flight(tmp_path):
    """The flight ring reaches disk at FIRE time, on the timer thread —
    before any interrupt/hard-exit handling."""
    from cpd_tpu.resilience import StepWatchdog
    path = str(tmp_path / "flight.jsonl")
    fr = FlightRecorder(path, capacity=8)
    fr.record("step", step=41, loss=2.5)
    wd = StepWatchdog(0.05, interrupt=False,
                      on_trip=lambda ctx: fr.dump("watchdog"))
    wd.arm(41, loss=2.5)
    time.sleep(0.4)
    wd.close()
    assert wd.tripped
    lines = [json.loads(ln) for ln in open(path)]
    assert lines[0]["reason"] == "watchdog"
    assert any(ln.get("step") == 41 for ln in lines[1:])


# ----------------------------------------------- run_guarded: obs is free

from types import SimpleNamespace


def _counting_step(state, x):
    import jax.numpy as jnp
    new = SimpleNamespace(step=state.step, w=state.w + jnp.asarray(x))
    return new, {"loss": float(np.asarray(state.w).sum())}


def test_run_guarded_bitwise_identical_with_obs(tmp_path):
    """Acceptance: obs-on leaves the guarded loop's outputs bitwise
    unchanged (and obs-off means no instrumentation exists at all)."""
    import jax.numpy as jnp
    from cpd_tpu.resilience import run_guarded

    def make():
        return SimpleNamespace(step=0, w=jnp.zeros((4,), jnp.float32))

    def nb(step, reseed):
        return (np.full((4,), 1.0 + step, np.float32),)

    s_off, rep_off = run_guarded(_counting_step, make(), nb, 5)
    tr = Tracer("guarded")
    fr = FlightRecorder(str(tmp_path / "f.jsonl"), capacity=16)
    s_on, rep_on = run_guarded(_counting_step, make(), nb, 5,
                               tracer=tr, flight=fr)
    assert np.array_equal(np.asarray(s_off.w), np.asarray(s_on.w))
    assert rep_off.counters == rep_on.counters
    assert rep_off.events == rep_on.events
    # the spans really were recorded: 5 data + 5 step
    names = [s[1] for s in tr.spans]
    assert names.count("data") == 5 and names.count("step") == 5
    assert len(fr) == 5


def test_run_guarded_abort_dumps_flight(tmp_path):
    from cpd_tpu.resilience import DivergenceSentinel, run_guarded

    calls = {"n": 0}

    def diverging_step(state, x):
        calls["n"] += 1
        return state, {"loss": 1.0 if calls["n"] < 3 else 1e9}

    fr = FlightRecorder(str(tmp_path / "f.jsonl"), capacity=16)
    _s, rep = run_guarded(diverging_step, SimpleNamespace(step=0),
                          lambda s, r: (0,), 10,
                          sentinel=DivergenceSentinel(2, factor=10),
                          flight=fr)
    assert rep.aborted == "diverged"
    lines = [json.loads(ln) for ln in open(str(tmp_path / "f.jsonl"))]
    assert lines[0]["reason"] == "diverged"
    assert any(ln.get("kind") == "abort" for ln in lines[1:])


# ------------------------------------------- serve: free + exact timelines

VOCAB = 64
ENGINE_KW = dict(n_slots=2, max_seq=32, page_size=8, prefill_chunk=4)


@pytest.fixture(scope="module")
def serve_model():
    import jax
    import jax.numpy as jnp
    from cpd_tpu.models import transformer_lm
    model = transformer_lm(vocab_size=VOCAB, d_model=32, n_layers=2,
                           n_heads=4, n_kv_heads=2, d_ff=64)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _serve_trace(n=6):
    from cpd_tpu.serve import mixed_trace, with_sla
    return with_sla(
        mixed_trace(n, VOCAB, prompt_lens=(4, 6), max_new=(4,), seed=5),
        [dict(sla_class=0), dict(sla_class=1, deadline_steps=64)])


def test_serve_obs_is_bitwise_free(serve_model):
    """Acceptance: tracer+flight attached vs not — identical counters,
    finished tokens and events (obs only observes)."""
    from cpd_tpu.serve import ServeEngine, run_trace
    model, params = serve_model
    trace = _serve_trace()

    def drive(**obs_kw):
        eng = ServeEngine(model, params, **ENGINE_KW, **obs_kw)
        m = run_trace(eng, list(trace))
        return eng, m

    e_off, m_off = drive()
    e_on, m_on = drive(tracer=Tracer("serve"),
                       flight=FlightRecorder(None, capacity=32))
    assert m_off["counters"] == m_on["counters"]
    assert e_off.finished == e_on.finished
    # same event sequence on the step clock (walls legitimately differ)
    assert [e[:3] for e in e_off.events] == [e[:3] for e in e_on.events]


def test_serve_timeline_reconstruction_is_exact(serve_model):
    """THE acceptance gate: reconstructed TTFT/TPOT/goodput/counts from
    the per-request timeline equal run_trace's published metrics
    exactly (same floats, same rounding)."""
    from cpd_tpu.serve import ServeEngine, run_trace, timeline_metrics
    model, params = serve_model
    trace = _serve_trace()
    tr = Tracer("serve")
    eng = ServeEngine(model, params, **ENGINE_KW, tracer=tr)
    pub = run_trace(eng, list(trace), sla_ttft_ms=500.0,
                    sla_tpot_ms=100.0)
    assert pub["counters"]["results_evicted"] == 0   # parity precondition
    rec = timeline_metrics(tr, sla_ttft_ms=500.0, sla_tpot_ms=100.0)
    for key in ("submitted", "completed", "shed", "deadline_misses",
                "dropped", "shed_rate", "deadline_miss_rate",
                "ttft_ms_p50", "ttft_ms_p99", "tpot_ms_p50",
                "tpot_ms_p99", "goodput_tok_per_s", "goodput_by_class",
                "tok_per_s", "duration_s"):
        assert rec[key] == pub[key], key
    assert rec["tokens_generated"] == \
        pub["counters"]["tokens_generated"]
    # the timeline carries the admission verdicts, and a full-window
    # run says so
    assert sum(rec["verdicts"].values()) == pub["submitted"]
    assert rec["timeline_truncated"] is False


def test_timeline_parity_holds_with_result_store_at_cap(serve_model):
    """ISSUE 13 satellite — the PR 11 parity caveat, closed: with the
    bounded `ResultStore` held AT CAP (evictions mid-run), the
    published metrics still equal the timeline reconstruction
    float-for-float, because `run_trace` now derives its per-request
    numbers from the timeline whenever a tracer is attached — a
    completed rid the store evicted keeps its true n_generated."""
    from cpd_tpu.serve import ServeEngine, run_trace, timeline_metrics
    model, params = serve_model
    trace = _serve_trace(12)
    tr = Tracer("serve", max_records=4096)
    eng = ServeEngine(model, params, **ENGINE_KW, finished_cap=2,
                      tracer=tr)
    pub = run_trace(eng, list(trace), sla_ttft_ms=500.0,
                    sla_tpot_ms=100.0)
    # the precondition the OLD caveat excluded: the store really
    # evicted finished entries mid-run
    assert pub["counters"]["results_evicted"] > 0
    assert len(eng.finished) <= 2
    # ... and the per-request metrics are NOT truncated by it anymore
    assert pub["metrics_truncated"] is False
    rec = timeline_metrics(tr, sla_ttft_ms=500.0, sla_tpot_ms=100.0)
    for key in ("submitted", "completed", "shed", "deadline_misses",
                "dropped", "shed_rate", "deadline_miss_rate",
                "ttft_ms_p50", "ttft_ms_p99", "tpot_ms_p50",
                "tpot_ms_p99", "goodput_tok_per_s", "goodput_by_class",
                "tok_per_s", "duration_s"):
        assert rec[key] == pub[key], key
    assert rec["tokens_generated"] == \
        pub["counters"]["tokens_generated"]
    assert rec["timeline_truncated"] is False


def test_run_trace_null_tracer_matches_tracerless_metrics(serve_model):
    """NULL_TRACER is the documented disabled path: `run_trace` must
    treat it exactly like ``tracer=None`` — store/event-derived
    published metrics, not an (empty) timeline derivation."""
    from cpd_tpu.obs.trace import NULL_TRACER
    from cpd_tpu.serve import ServeEngine, run_trace
    model, params = serve_model
    trace = _serve_trace()
    off = run_trace(ServeEngine(model, params, **ENGINE_KW),
                    list(trace))
    null = run_trace(ServeEngine(model, params, **ENGINE_KW,
                                 tracer=NULL_TRACER), list(trace))
    assert null["completed"] == off["completed"] == len(trace)
    # the real latency numbers are published (not None/0.0 from an
    # empty timeline); counters identical
    assert null["ttft_ms_p50"] is not None
    assert null["goodput_tok_per_s"] and null["goodput_tok_per_s"] > 0
    assert null["counters"] == off["counters"]


def test_timeline_metrics_without_run_trace_is_loud(serve_model):
    """An engine stepped manually records no step_begin walls —
    reconstruction must refuse (a silent wrong TTFT would betray the
    exactness contract) instead of KeyError-ing."""
    from cpd_tpu.serve import ServeEngine, timeline_metrics
    model, params = serve_model
    tr = Tracer("serve")
    eng = ServeEngine(model, params, **ENGINE_KW, tracer=tr)
    for r in _serve_trace(2):
        eng.submit(r)
    eng.run_until_drained()
    with pytest.raises(ValueError, match="no step_begin"):
        timeline_metrics(tr)


def test_serve_obs_run_exports_deterministically(serve_model, tmp_path):
    """Satellite: two runs of the same (trace, seed) produce
    byte-identical stripped JSONL + Chrome trace, and the Prometheus
    text parses."""
    from cpd_tpu.serve import ServeEngine, run_trace
    model, params = serve_model
    trace = _serve_trace()
    blobs = []
    for run in ("a", "b"):
        tr = Tracer("serve")
        reg = MetricsRegistry()
        eng = ServeEngine(model, params, **ENGINE_KW, tracer=tr)
        run_trace(eng, list(trace))
        reg.absorb_serve_counters(eng.counters)
        j = export_jsonl(tr, str(tmp_path / f"{run}.jsonl"),
                         strip_wall=True)
        c = export_chrome_trace(tr, str(tmp_path / f"{run}.json"),
                                strip_wall=True)
        p = export_prometheus(reg, str(tmp_path / f"{run}.prom"))
        blobs.append((open(j, "rb").read(), open(c, "rb").read(), p))
    assert blobs[0] == blobs[1]
    parsed = parse_prometheus(blobs[0][2])
    assert parsed["cpd_serve_completed"]["samples"][0][1] == \
        len(_serve_trace())


def test_serve_snapshot_dumps_flight(serve_model, tmp_path):
    from cpd_tpu.serve import ServeEngine
    model, params = serve_model
    fr = FlightRecorder(str(tmp_path / "flight.jsonl"), capacity=16)
    eng = ServeEngine(model, params, **ENGINE_KW, flight=fr)
    for r in _serve_trace(2):
        eng.submit(r)
    for _ in range(4):
        eng.step()
    eng.snapshot(str(tmp_path / "snap"))
    lines = [json.loads(ln)
             for ln in open(str(tmp_path / "flight.jsonl"))]
    assert lines[0]["reason"] == "snapshot"
    assert any(ln.get("kind") == "serve_step" for ln in lines[1:])
