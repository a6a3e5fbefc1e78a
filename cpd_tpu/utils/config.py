"""YAML-into-argparse config merge (ResNet18 trainer parity) + the
shared resilience-flag surface.

The reference loads a YAML file and injects the ``common:`` block's keys
directly onto the argparse namespace (mix.py:69-72), so CLI flags and YAML
keys share one flat namespace.  Same contract here, plus explicit
precedence: a key given on the command line wins over the YAML value.

``add_resilience_flags`` / ``build_resilience`` give every trainer the
same ``--fault-plan`` / guard / watchdog / rollback vocabulary (the YAML
merge covers these keys too, since they are plain argparse dests).
Imports of the resilience package are lazy: a trainer that never passes
a resilience flag pays nothing.
"""

from __future__ import annotations

import argparse
from typing import Any, Dict

import yaml

__all__ = ["load_yaml_config", "merge_config_into_args",
           "add_resilience_flags", "add_transport_flags",
           "add_obs_flags", "build_obs", "finish_obs",
           "build_resilience", "overlap_key"]


def load_yaml_config(path: str, section: str = "common") -> Dict[str, Any]:
    """Read `path` and return its `section` mapping (mix.py:69-72 reads the
    ``common`` block of configs/res18_cifar.yaml)."""
    with open(path) as f:
        doc = yaml.safe_load(f) or {}
    cfg = doc.get(section, doc)
    if not isinstance(cfg, dict):
        raise ValueError(f"config section {section!r} in {path} is not a map")
    return cfg


def merge_config_into_args(args: argparse.Namespace, cfg: Dict[str, Any],
                           cli_overrides: Dict[str, Any] | None = None
                           ) -> argparse.Namespace:
    """Set each cfg key as an attribute on `args` unless the user passed it
    explicitly on the command line (keys in `cli_overrides`)."""
    explicit = cli_overrides or {}
    for key, value in cfg.items():
        if key not in explicit:
            setattr(args, key, value)
    return args


def add_transport_flags(parser: argparse.ArgumentParser) -> None:
    """The shared gradient-transport knobs (ISSUE 8: overlapped
    backward-reduce + bucket sizing), one surface for every trainer."""
    g = parser.add_argument_group(
        "transport", "gradient-reduce transport (parallel/overlap.py)")
    g.add_argument("--overlap-reduce", action="store_true",
                   help="bucketed, dependency-scheduled reduction: run "
                        "each gradient bucket's quantized all-reduce "
                        "INSIDE the backward pass (custom_vjp taps) the "
                        "moment the bucket's last gradient closes, so "
                        "XLA can overlap ring hops with backward "
                        "compute.  Bitwise identical to the "
                        "post-backward reduction.  Composes with "
                        "--emulate_node > 1 (unrolled micro chain "
                        "feeding the last micro-batch's taps) and with "
                        "--zero1/--zero2 (ZeRO-2 runs its per-bucket "
                        "all_to_all reduce-scatter inside the taps)")
    g.add_argument("--bucket-elems", default=None, type=int,
                   help="per-bucket element cap for the bucketed "
                        "faithful gather, the bucketed ring and the "
                        "overlapped schedule (default: parallel/dist."
                        "_BUCKET_ELEMS = 4M).  Smaller buckets close "
                        "earlier in the backward (more overlap) but "
                        "launch more collectives — sweep with "
                        "tools/bench_reduce.py --bucket-sweep")
    g.add_argument("--block-scale", action="store_true",
                   help="block-scaled ring wire (EQuARX-style, ISSUE 9): "
                        "every hop cast shares one power-of-2 scale per "
                        "--block-size consecutive elements; the 1-byte-"
                        "per-block shift sidecar rides the packed wire. "
                        "Recovers per-tensor-e5m7-class accuracy at e4m3 "
                        "wire bytes (tools/bench_reduce.py --block-sweep)."
                        "  Requires --mode ring and a packable gradient "
                        "format (man >= 2)")
    g.add_argument("--block-size", default=128, type=int,
                   help="elements per shared-scale block for "
                        "--block-scale (default 128; multiples of 128 "
                        "keep the fused Pallas wire kernel eligible — "
                        "other sizes fall back to the XLA hop bodies)")


def overlap_key(args: argparse.Namespace):
    """The `ladder_step_key(overlap=...)` coordinate for a parsed CLI:
    ``(overlap_reduce, bucket_elems)`` when the run touches the overlap
    surface, None otherwise (keeping the PR 4/5-compatible key shapes
    for runs that never saw the flags)."""
    ov = bool(getattr(args, "overlap_reduce", False))
    be = getattr(args, "bucket_elems", None)
    if not ov and be is None:
        return None
    return (ov, be)


def block_key(args: argparse.Namespace):
    """The `ladder_step_key(block=...)` coordinate for a parsed CLI:
    ``(block_scale, block_size)`` when the run turned block scaling on,
    None otherwise (keeping the PR 8-compatible key shapes for runs
    that never saw the flags).  Unlike `overlap_key`, a bare
    ``--block-size`` without ``--block-scale`` stays None — the size is
    inert until the sidecar wire exists."""
    if not bool(getattr(args, "block_scale", False)):
        return None
    return (True, int(getattr(args, "block_size", 128)))


def add_obs_flags(parser: argparse.ArgumentParser) -> None:
    """The shared observability surface (docs/OBSERVABILITY.md): every
    trainer/bench CLI speaks the same two flags."""
    g = parser.add_argument_group(
        "observability", "cpd_tpu.obs tracing / metrics / flight "
                         "recorder")
    g.add_argument("--obs-dir", default=None, metavar="DIR",
                   help="enable the obs spine: step/request tracing + "
                        "the metrics registry, exported into DIR on "
                        "exit as events.jsonl (deterministic event "
                        "stream), metrics.prom (Prometheus text) and "
                        "trace.json (Perfetto/Chrome-trace).  Unset = "
                        "zero instrumentation cost; either way step "
                        "outputs are bitwise unchanged (obs only "
                        "observes)")
    g.add_argument("--obs-flight", default=256, type=int,
                   metavar="N",
                   help="flight-recorder ring capacity (with "
                        "--obs-dir): the last N step events are "
                        "dumped to DIR/flight.jsonl on watchdog fire, "
                        "rollback, preemption or serve snapshot "
                        "(0 disables the recorder)")


def build_obs(args: argparse.Namespace, *, run: str,
              meta: Dict[str, Any] | None = None) -> Dict[str, Any]:
    """Materialize the obs stack from parsed flags: ``tracer`` /
    ``registry`` / ``flight`` (each None when --obs-dir is unset — the
    provably-free disabled path) plus ``finish(extra=...)``, which
    writes the artifact bundle and returns its paths+summary dict (or
    None when obs is off)."""
    import os

    obs_dir = getattr(args, "obs_dir", None)
    if not obs_dir:
        return {"tracer": None, "registry": None, "flight": None,
                "dir": None, "active": False,
                "finish": lambda **_kw: None}
    from cpd_tpu.obs import FlightRecorder, MetricsRegistry, Tracer
    cap = int(getattr(args, "obs_flight", 256) or 0)
    # with --profile-dir too, the spans also land in the profiler's trace
    tracer = Tracer(run, meta=meta,
                    annotate=bool(getattr(args, "profile_dir", None)))
    registry = MetricsRegistry()
    flight = (FlightRecorder(os.path.join(obs_dir, "flight.jsonl"),
                             capacity=cap) if cap > 0 else None)

    def finish(**extra):
        from cpd_tpu.obs import write_all
        out = write_all(obs_dir, tracer, registry)
        if extra:
            out["summary"].update(extra)
        return out

    return {"tracer": tracer, "registry": registry, "flight": flight,
            "dir": obs_dir, "active": True, "finish": finish}


def finish_obs(obs: Dict[str, Any], *, meter=None, last=None,
               step_no=None, supervisor=None, precision=None,
               elastic=None, rank: int = 0, **extra):
    """The ONE trainer obs epilogue (shared by the lm and resnet18
    CLIs): absorb the run counters, the final step's telemetry
    families and the supervisors' ladder state into the registry, then
    write the artifact bundle.  Returns the bundle dict, or None when
    obs is off."""
    if not obs["active"]:
        return None
    reg = obs["registry"]
    if meter is not None:
        reg.absorb_resilience_meter(meter)
    if last:
        reg.absorb_step_metrics(last, step_no)
    if supervisor is not None:
        reg.absorb_supervisor("transport", {
            "mode": supervisor.mode, "home": supervisor.home,
            "degraded": supervisor.degraded,
            "transitions": supervisor.transitions})
    if precision is not None:
        reg.absorb_supervisor("precision", precision.state_dict())
    if elastic is not None:
        reg.absorb_elastic(elastic)
    out = obs["finish"](**extra)
    if rank == 0:
        import sys
        print(f"=> obs artifacts in {out['dir']}", file=sys.stderr)
    return out


def add_resilience_flags(parser: argparse.ArgumentParser) -> None:
    """The shared ``--fault-plan`` + defense knobs (docs/RESILIENCE.md)."""
    g = parser.add_argument_group(
        "resilience", "fault injection + guarded-loop defenses")
    g.add_argument("--fault-plan", default=None, metavar="SPEC|FILE",
                   help="inject faults: 'kind@step[:arg];...' (e.g. "
                        "'grad_nan@3;stall@5:1.5;ckpt_truncate@6'), a "
                        "JSON plan file, or 'random:<seed>' for a "
                        "seed-deterministic random plan over the run")
    g.add_argument("--guard-grads", action="store_true",
                   help="wrap the optimizer with resilience."
                        "with_grad_guard: skip non-finite / spiking / "
                        "replica-disagreeing gradient steps (implied by "
                        "--fault-plan with grad_* faults)")
    g.add_argument("--spike-factor", default=10.0, type=float,
                   help="guard: skip a finite step whose grad norm "
                        "exceeds this multiple of its running EMA")
    g.add_argument("--watchdog-timeout", default=0.0, type=float,
                   help="seconds a step may block before the watchdog "
                        "dumps diagnostics and forces a clean "
                        "checkpoint-and-exit (0 = off)")
    g.add_argument("--divergence-window", default=0, type=int,
                   help="divergence sentinel window of recent losses "
                        "(0 = off); trips when loss > factor x median")
    g.add_argument("--divergence-factor", default=10.0, type=float)
    g.add_argument("--divergence-mode", default="median",
                   choices=["median", "ema"],
                   help="sentinel detector: 'median' = factor x window-"
                        "median spike (default, PR-2 behavior); 'ema' = "
                        "dual-EMA relative drift — catches the SLOW "
                        "upward creep of quiet saturation that drags "
                        "the median up with it (use a smaller factor, "
                        "e.g. 2)")
    g.add_argument("--max-rollbacks", default=2, type=int,
                   help="bounded retries: rollbacks to the newest valid "
                        "checkpoint before declaring the run diverged")
    g.add_argument("--rollback-backoff", default=0.0, type=float,
                   help="seconds to sleep after rollback k (doubled "
                        "each retry)")
    g.add_argument("--no-ckpt-integrity", dest="ckpt_integrity",
                   action="store_false", default=True,
                   help="skip the per-save content digest (saves regain "
                        "their async overlap with compute, at the cost "
                        "of restore falling back only on restore "
                        "FAILURES, not on silent corruption)")
    g.add_argument("--verify-reduce", action="store_true",
                   help="self-verifying quantized reduction "
                        "(parallel/integrity.py): tagged checksums on "
                        "every ring hop + all-gather row, cross-replica "
                        "agreement digest, and the degraded-transport "
                        "ladder (ring -> faithful -> fp32) on failure")
    g.add_argument("--reduce-retries", default=1, type=int,
                   help="verified reduce: same-step retries before the "
                        "transport supervisor downgrades a level")
    g.add_argument("--transport-probation", default=8, type=int,
                   help="clean verified steps at a degraded transport "
                        "before probation moves one level back up")
    g.add_argument("--precision-ladder", default=None, metavar="F1,F2,..",
                   help="eXmY format-escalation ladder (resilience."
                        "precision): comma list of rungs, home first "
                        "and range-widening (e.g. 'e5m2,e5m7,e8m23'; "
                        "the home rung must equal --grad_exp/"
                        "--grad_man).  Turns on the reduce-wire "
                        "numeric-health telemetry and escalates the "
                        "gradient format when the agreed sat+NaN rate "
                        "stays hot; quiet steps probation back down, "
                        "never below home; ladder state persists in "
                        "checkpoints")
    g.add_argument("--sat-threshold", default=1e-3, type=float,
                   help="precision ladder: agreed (sat+NaN)/total rate "
                        "at the reduce wire above which a step is hot")
    g.add_argument("--sat-patience", default=2, type=int,
                   help="precision ladder: consecutive hot steps before "
                        "escalating one rung")
    g.add_argument("--precision-probation", default=16, type=int,
                   help="precision ladder: consecutive quiet steps at "
                        "an escalated rung before stepping one rung "
                        "back down")
    g.add_argument("--quant-telemetry", action="store_true",
                   help="reduce-wire numeric-health counters "
                        "(prec_wire_sat/underflow/nan + aps_bad "
                        "metrics) WITHOUT the ladder — observability "
                        "only (implied by --precision-ladder)")
    g.add_argument("--elastic", action="store_true",
                   help="elastic training (resilience.elastic): "
                        "heartbeat/straggler detection per host, "
                        "in-step link retries, deterministic mesh "
                        "shrink to the largest power-of-two world of "
                        "alive hosts through the digest-sealed "
                        "checkpoints, probationary regrow on rejoin "
                        "(arms host_kill/straggler/link_flaky plan "
                        "kinds)")
    g.add_argument("--heartbeat-patience", default=3, type=int,
                   help="elastic: consecutive slow heartbeats before a "
                        "host is hot and gets drained")
    g.add_argument("--straggler-factor", default=2.0, type=float,
                   help="elastic: a heartbeat slower than this multiple "
                        "of the host's own step-time EMA is slow")


def build_resilience(args: argparse.Namespace, *, n_steps: int,
                     rank: int = 0, world: int = 0) -> Dict[str, Any]:
    """Materialize the resilience stack from parsed flags.

    Returns a dict with ``injector`` / ``watchdog`` / ``sentinel`` /
    ``meter`` (each possibly None) and ``wrap_tx``, a callable that
    layers ``with_fault_injection`` (when the plan has gradient faults)
    and ``with_grad_guard`` (when requested or implied) around an
    optimizer — outermost-first, the order guard.py documents.

    ``world``: the data-parallel host count — needed only when
    ``--elastic`` is on (the ElasticSupervisor watches that many
    heartbeats); trainers that don't pass it get ``"elastic": None``
    and a warning if the flag was set.
    """
    from cpd_tpu.resilience import (DivergenceSentinel, FaultPlan,
                                    Injector, StepWatchdog,
                                    with_fault_injection, with_grad_guard)
    from cpd_tpu.train.metrics import ResilienceMeter

    plan = None
    spec = getattr(args, "fault_plan", None)
    if spec:
        if spec.startswith("random:"):
            plan = FaultPlan.random(int(spec.split(":", 1)[1]), n_steps)
        else:
            plan = FaultPlan.parse(spec)
    guard = bool(getattr(args, "guard_grads", False)
                 or (plan is not None and plan.grad_faults()))

    def wrap_tx(tx, axis_name=None):
        if guard:
            tx = with_grad_guard(tx, spike_factor=args.spike_factor,
                                 axis_name=axis_name)
        if plan is not None and plan.grad_faults():
            tx = with_fault_injection(tx, plan, n_steps,
                                      axis_name=axis_name)
        return tx

    timeout = float(getattr(args, "watchdog_timeout", 0.0) or 0.0)
    window = int(getattr(args, "divergence_window", 0) or 0)
    verify = bool(getattr(args, "verify_reduce", False))
    wire = plan.wire_faults() if plan is not None else ()
    if wire and not verify:
        # the attack without the defense silently corrupts sums — legal
        # (that IS the baseline the checksums are measured against) but
        # never what a CLI user means; make the footgun explicit
        import sys as _sys
        print("=> WARNING: fault plan schedules wire_* faults but "
              "--verify-reduce is off — the corrupted reduce will go "
              "UNDETECTED (pass --verify-reduce to arm the checksums)",
              file=_sys.stderr)
    supervisor = None
    if verify:
        from cpd_tpu.resilience.transport import TransportSupervisor
        start = getattr(args, "mode", "faithful")
        if start in TransportSupervisor.LEVELS:
            supervisor = TransportSupervisor(
                start=start, max_retries=int(args.reduce_retries),
                probation=int(args.transport_probation))
        # modes outside the ladder (e.g. fast) keep THEIR reduction and
        # verify by agreement digest only — detection without a ladder,
        # never a silent swap onto a transport the user didn't configure
    precision = None
    ladder_spec = getattr(args, "precision_ladder", None)
    if ladder_spec:
        from cpd_tpu.resilience.precision import (PrecisionSupervisor,
                                                  format_name)
        precision = PrecisionSupervisor(
            ladder_spec, threshold=float(args.sat_threshold),
            patience=int(args.sat_patience),
            probation=int(args.precision_probation))
        ge = getattr(args, "grad_exp", None)
        gm = getattr(args, "grad_man", None)
        if ge is not None and precision.home != (int(ge), int(gm)):
            # the ladder's rung 0 IS the run's gradient format; a
            # mismatch would silently train at a format the flags deny
            raise ValueError(
                f"--precision-ladder home rung "
                f"{format_name(precision.home)} must equal the "
                f"configured gradient format e{ge}m{gm} "
                f"(--grad_exp/--grad_man); put e{ge}m{gm} first")
        if getattr(args, "mode", None) == "ring":
            # fail at argument time, not hours in: the ring transport's
            # packed wire (quant.numerics.pack_exmy) needs man_bits >= 2
            # for its Inf/carry/NaN special codes, and the lazily
            # compiled escalated step would otherwise hit that
            # ValueError inside jit tracing at the exact moment the
            # ladder tries to save the run
            unpackable = [f for f in precision.ladder
                          if f[1] < 2 and f != (8, 23)]
            if unpackable:
                raise ValueError(
                    f"--precision-ladder rung(s) "
                    f"{[format_name(f) for f in unpackable]} cannot "
                    f"ride the ring transport's packed wire (pack_exmy "
                    f"needs man_bits >= 2 for the special codes); use "
                    f"man >= 2 rungs or --mode faithful")
    sat = plan.sat_faults() if plan is not None else ()
    quant_stats = bool(precision is not None
                       or getattr(args, "quant_telemetry", False))
    elastic = None
    wants_elastic = bool(getattr(args, "elastic", False))
    host_faults = plan.elastic_faults() if plan is not None else ()
    if host_faults and not wants_elastic:
        import sys as _sys
        print("=> WARNING: fault plan schedules host-level faults "
              "(host_kill/straggler/link_flaky) but --elastic is off — "
              "they will be flagged unfired, not survived (pass "
              "--elastic to arm the recovery ladder)", file=_sys.stderr)
    if wants_elastic:
        if world >= 1:
            from cpd_tpu.resilience.elastic import ElasticSupervisor
            elastic = ElasticSupervisor(
                world,
                patience=int(getattr(args, "heartbeat_patience", 3)),
                factor=float(getattr(args, "straggler_factor", 2.0)))
        elif rank == 0:
            import sys as _sys
            print("=> WARNING: --elastic needs the trainer to pass its "
                  "host world to build_resilience(world=...); elastic "
                  "supervision is OFF for this run", file=_sys.stderr)
    return {
        "plan": plan,
        "verify": verify,
        "wire_plan": (plan.wire_schedule(n_steps) if wire else None),
        "supervisor": supervisor,
        # precision-ladder surface (ISSUE 5): the supervisor (None when
        # --precision-ladder is off), whether step builders should
        # thread the prec_wire_* telemetry, and the baked 2^k
        # saturation-pressure table (None when the plan has no
        # sat_pressure specs)
        "precision": precision,
        "quant_stats": quant_stats,
        "sat_plan": (plan.sat_schedule(n_steps) if sat else None),
        # True only when wrap_tx is not the identity — what actually
        # composes (or not) with custom-update paths like ZeRO
        "wraps_optimizer": bool(guard
                                or (plan is not None and plan.grad_faults())),
        "injector": Injector(plan, rank=rank) if plan is not None else None,
        # hard_exit_after: a trip nobody acknowledges (step wedged in
        # native code, or the interrupt absorbed with no boundary in
        # sight) kills the process with diagnostics after one more
        # timeout, instead of hanging forever (watchdog.py docstring)
        "watchdog": (StepWatchdog(timeout, rank=rank,
                                  hard_exit_after=timeout)
                     if timeout > 0 else None),
        "sentinel": (DivergenceSentinel(window,
                                        factor=args.divergence_factor,
                                        mode=getattr(args,
                                                     "divergence_mode",
                                                     "median"))
                     if window > 0 else None),
        "meter": ResilienceMeter(),
        "wrap_tx": wrap_tx,
        # elastic-training surface (ISSUE 19): the ElasticSupervisor
        # (None unless --elastic AND the trainer passed world >= 1)
        "elastic": elastic,
        "active": bool(plan or guard or timeout > 0 or window > 0
                       or verify or quant_stats or elastic is not None),
    }
