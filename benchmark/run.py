"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds every chip of the run.  It refuses any backend
but a TPU (exit 2, nothing computed), makes weights and a ring of seeded
batches on the device, checks the first loss against the configuration's
plain reference, warms the one step program, measures for `--seconds`
(`--trace 0`: the cell's end-to-end metrics) or records a profiler trace
of a short steady stretch (`--trace 1`: its per-layer metrics), and then,
with the window closed and the device's memory read, holds the timed
step's first three updates to the plain reference's own steps
(`check.py`).  It prints ONE JSON object as the last line of stdout, and
each number compared beside its limit as the last lines of stderr.
Anything that raises ends the run with a traceback and a nonzero exit.

Everything that belongs to one cell, configuration or metric is a file
found by the name `BENCHMARK.json` gives (see README.md); no such name
appears in this file.  `--list` prints what was found and touches no
device.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()   # process start, on `obs.timing.now`'s clock

import argparse
import contextlib
import importlib
import json
import math
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(ref: str, package: str):
    """`module:function` under `benchmark/<package>/`."""
    module, func = ref.split(":")
    return getattr(importlib.import_module(f"benchmark.{package}.{module}"),
                   func)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def discover(root: str = ROOT) -> dict:
    """Every cell of BENCHMARK.json with the files it resolves to; fails
    on a name it cannot find."""
    from benchmark import check

    bench = load_json(root, "BENCHMARK.json")
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {}
    for w in bench["workloads"]:
        config = load_json(root, configs[w["config"]]["file"])
        traffic = load_json(HERE, "traffic", w["traffic"] + ".json")
        metrics = {}
        for kind in ("end_to_end", "per_layer"):
            metrics[kind] = {
                m["name"]: {**load_json(HERE, "metrics", m["name"] + ".json"),
                            "unit": m["unit"]}
                for m in bench[kind] if applies(m, w["name"])}
        for need in ("runner", "ops_per_item", "reference", "head_part"):
            if need not in config:
                raise KeyError(f"{configs[w['config']]['file']}: no {need!r}")
        try:
            check.limits_stated(traffic)
        except KeyError as e:
            raise KeyError(f"traffic {w['traffic']!r}: {e.args[0]}") from None
        if not os.path.exists(os.path.join(
                HERE, "runners", config["runner"] + ".py")):
            raise FileNotFoundError(f"no runner {config['runner']!r}")
        cells[w["name"]] = {"cell": w, "config": config, "traffic": traffic,
                            "metrics": metrics}
    return cells


class CompileCounter:
    """Counts jax's trace and backend-compile events and the persistent
    cache's requests and hits, from `install()` on."""

    def __init__(self):
        self.compiles = self.traces = self.requests = self.hits = 0

    def _on_duration(self, event, secs, **_):
        if event == _TRACE_EVENT:
            self.traces += 1
        elif event == _COMPILE_EVENT:
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == _CACHE_REQUEST:
            self.requests += 1
        elif event == _CACHE_HIT:
            self.hits += 1

    def install(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def snapshot(self) -> dict:
        return {"traces": self.traces, "backend_compiles": self.compiles,
                "cache_requests": self.requests, "cache_hits": self.hits}


def seed_key(seed: int):
    """A raw threefry key from a seed of up to 64 bits (the driver's seeds
    pass 2**31, which `jax.random.PRNGKey` refuses without x64)."""
    import jax.numpy as jnp
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)


def replica_checksums(state, mesh):
    """One float per device: the sum of |parameter| over that device's
    copy.  A reduction every rank agrees on leaves them identical."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from cpd_tpu.compat import shard_map

    def local(params):
        total = sum(jnp.abs(l.astype(jnp.float32)).sum()
                    for l in jax.tree.leaves(params))
        return total[None]

    return jax.device_get(jax.jit(shard_map(
        local, mesh=mesh, in_specs=(P(),), out_specs=P("dp"),
        check_vma=False))(state.params))


def device_memory_stats(devices: list) -> list:
    """Each device's `memory_stats()`; a backend that reports none (the
    CPU's) cannot be measured, and raises."""
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        raise RuntimeError(f"{devices[0].platform}: the runtime reports no "
                           f"memory statistics")
    return stats


def peak_bytes(stats: dict) -> int:
    """The most one chip held: the runtime's peak of live buffers plus
    the peak it reserved for running programs' temporaries.  On a v5e
    `peak_bytes_in_use` leaves the second out (the LM step reads 4.5 GB
    there where its program needs 10 GiB), and the runtime counts it
    under `bytes_reserved`."""
    return stats["peak_bytes_in_use"] + stats.get(
        "peak_bytes_reserved", stats.get("bytes_reserved", 0))


def build(found: dict, devices: list) -> dict:
    """The cell's mesh and runner, and its two makers jitted: `init`
    (key -> replicated state) and `make_batch` (key -> a global batch
    split over `dp`).  Nothing touches a device yet."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cpd_tpu.parallel.mesh import make_mesh

    config, traffic = found["config"], found["traffic"]
    chips = found["cell"]["chips"]
    mesh = make_mesh(dp=chips, devices=devices[:chips])
    reference = resolve(config["reference"], "reference")
    runner = importlib.import_module(
        f"benchmark.runners.{config['runner']}").build(
            config, traffic, mesh, reference)
    by_batch = NamedSharding(mesh, P("dp"))
    return {"mesh": mesh, "runner": runner,
            "init": jax.jit(runner.init_state,
                            out_shardings=NamedSharding(mesh, P())),
            "make_batch": jax.jit(runner.make_batch,
                                  out_shardings=(by_batch, by_batch))}


def set_up(found: dict, seed: int, devices: list):
    """Mesh, runner, state and batches on the device, the reference's loss
    for the first batch, and the warmed step.  Returns what the window
    needs plus the facts `correct` is decided on."""
    import jax

    from cpd_tpu.obs.timing import now

    marks = [("start", now())]
    built = build(found, devices)
    mesh, runner, init = built["mesh"], built["runner"], built["init"]
    k_weights, k_data = jax.random.split(seed_key(seed))
    state = init(k_weights)
    batches = [built["make_batch"](jax.random.fold_in(k_data, i))
               for i in range(found["traffic"].get("ring", 8))]
    param_count = sum(l.size for l in jax.tree.leaves(state.params))
    jax.block_until_ready((state, batches))
    marks.append(("weights_and_batches", now()))

    # before the first step: the step donates its state
    ref_loss = float(jax.jit(runner.reference_loss)(state, *batches[0]))
    marks.append(("reference", now()))
    state, metrics = runner.step(state, *batches[0])
    first_loss = float(metrics["loss"])
    marks.append(("first_step", now()))     # trace, lower, compile or load
    for a, b in batches[1:3]:               # settle donation and layouts
        state, metrics = runner.step(state, a, b)
    jax.block_until_ready((state, metrics))
    marks.append(("two_more_steps", now()))
    return {"mesh": mesh, "runner": runner, "state": state,
            "batches": batches, "param_count": param_count,
            "init": init, "key_weights": k_weights,
            "first_loss": first_loss, "reference_loss": ref_loss,
            "phases_s": {name: t - t0 for (_, t0), (name, t)
                         in zip(marks, marks[1:])}}


def _nothing_compiled(counts: dict) -> bool:
    return (counts["traces"] == 0 and counts["backend_compiles"] == 0
            and counts["cache_requests"] == 0)


def judge(found: dict, ready: dict, losses, in_window: dict,
          checksums, readings: dict, step_compiled: dict) -> tuple:
    """The comparison that decides `correct`: `(checks, compared)`, one
    named check each, and every number compared beside its limit."""
    from benchmark import check

    config = found["config"]
    unit = math.log(config["classes"])
    lo, hi = config["init_loss_band"]
    first, ref = ready["first_loss"], ready["reference_loss"]
    first_gap = abs(first - ref) / abs(ref) if ref else math.inf
    agree = checksums is None or all(c == checksums[0] for c in checksums)
    # printed beside the check; a NaN agrees with nothing, itself
    # included, and its distance is infinite
    spread = 0.0 if agree else max(
        (d if d == d else math.inf)
        for d in (abs(c - checksums[0]) for c in checksums))
    checks = {
        "losses_finite": all(math.isfinite(x) for x in losses),
        "init_loss_in_band": lo * unit <= first <= hi * unit,
        "matches_reference": first_gap <= config["reference_loss_rtol"],
        "nothing_compiled_in_window": _nothing_compiled(in_window),
        "replicas_agree": agree,
        "check_reran_the_timed_step": _nothing_compiled(step_compiled),
    }
    compared = {
        "first_loss_gap": {"value": first_gap,
                           "limit": config["reference_loss_rtol"]},
        "replica_checksum_spread": {"value": spread, "limit": 0.0}}
    held, limits = check.judge(readings, found["traffic"])
    return {**checks, **held}, {**compared, **limits}


def plain(value):
    """`value` with every float that JSON cannot hold as a string."""
    if isinstance(value, float) and not math.isfinite(value):
        return repr(value)
    if isinstance(value, dict):
        return {k: plain(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [plain(v) for v in value]
    return value


def traced_window(ready: dict, traffic: dict, keep_trace):
    """The measured loop under `jax.profiler` for `trace_seconds`:
    `(window, reduced trace, scope table)`, read while the trace's
    directory still exists."""
    import jax

    from benchmark import loop, trace_reduce, trace_scopes

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # host spans only, no call stacks
    with contextlib.ExitStack() as stack:
        trace_dir = keep_trace or stack.enter_context(
            tempfile.TemporaryDirectory())
        with jax.profiler.trace(trace_dir, profiler_options=options):
            window = loop.measure(
                ready["runner"].step, ready["state"], ready["batches"],
                traffic["group"], traffic.get("trace_seconds", 3.0),
                annotate=True)
        tables = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        scopes = trace_scopes.load_scopes(trace_dir, tables)
        if keep_trace:
            with open(os.path.join(trace_dir, "tables.json"), "w") as f:
                json.dump(tables, f)
    return window, trace_reduce.reduce(tables), scopes


def drive(found: dict, args, devices: list, peaks: dict,
          counter: CompileCounter, memory_stats=device_memory_stats) -> dict:
    """Everything of a run after the look for a chip: set-up, the window,
    the check, the readers; returns the result line.  `memory_stats` is
    a test's to replace, on a backend that reports none."""
    import jax

    from benchmark import check, loop, trace_scopes
    from cpd_tpu.obs.timing import now

    chips = found["cell"]["chips"]
    before_set_up = now() - _T0      # imports, backend start-up, discovery
    ready = set_up(found, args.seed, devices)
    traffic, runner = found["traffic"], ready["runner"]
    before = counter.snapshot()
    setup_seconds = now() - _T0
    if args.trace:
        window, trace, scopes = traced_window(ready, traffic, args.keep_trace)
    else:
        window = loop.measure(runner.step, ready["state"], ready["batches"],
                              traffic["group"], args.seconds)
        trace = scopes = None
    after = counter.snapshot()
    in_window = {k: after[k] - before[k] for k in after}

    losses = [float(x) for x in jax.device_get(window.losses)]
    checksums = (None if chips == 1 else
                 [float(c) for c in replica_checksums(window.state,
                                                      ready["mesh"])])
    stats = memory_stats(devices[:chips])
    memory_peak = max(map(peak_bytes, stats))

    # the window is closed and the memory read: nothing of the check is
    # in a metric.  Free the window's state, then step program and
    # reference from the seed's weights
    last_metrics = {k: float(v) for k, v in
                    jax.device_get(window.last_metrics).items()}
    window.state = window.last_metrics = ready["state"] = None
    t_check = now()
    readings, check_facts = check.first_steps(
        runner, found["config"], ready["init"],
        ready["key_weights"], ready["batches"], counter)
    check_seconds = now() - t_check
    checks, compared = judge(found, ready, losses, in_window, checksums,
                             readings, check_facts["step_compiled"])

    ctx = {"window": window, "chips": chips, "setup_seconds": setup_seconds,
           "items_per_step": runner.items_per_step,
           "config": found["config"], "traffic": traffic,
           "peaks": peaks, "trace": trace, "scopes": scopes,
           "memory_peak_bytes": memory_peak,
           "param_count": ready["param_count"],
           "last_metrics": last_metrics, "readings": readings}
    metrics = {}
    for name, spec in found["metrics"][
            "per_layer" if args.trace else "end_to_end"].items():
        value = resolve(spec["reader"], "readers")(ctx, spec.get("args", {}))
        if value is not None:       # a reader with nothing to read
            metrics[name] = {"value": value, "unit": spec["unit"]}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    line = {"correct": all(checks.values()), "attempted": window.steps,
            "failed": sum(not math.isfinite(x) for x in losses),
            "metrics": metrics, "device": device}
    facts = {
        "checks": checks, "first_loss": ready["first_loss"],
        "reference_loss": ready["reference_loss"],
        "last_loss": losses[-1], "step_samples": len(window.step_samples),
        "window_s": window.seconds, "setup": before, "in_window": in_window,
        "phases_s": {"before_set_up": before_set_up, **ready["phases_s"],
                     "update_check": check_seconds},
        "param_count": ready["param_count"], "chips_used": chips,
        "memory_stats": stats[0], "last_metrics": last_metrics,
        **readings, **check_facts,
        "first_step_repeats":
        check_facts["first_loss_again"] == ready["first_loss"]
        and check_facts["step_losses"][0] == ready["first_loss"]}
    if trace:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"],
                             "device_scopes": trace_scopes.top_scopes(scopes)}
        facts.update(
            scopes_found=scopes["scopes_found"],
            wire_dtypes=scopes["collectives"]["dtypes"],
            traced_rate_per_chip=window.steps * runner.items_per_step
            / window.seconds / chips)
    line["facts"] = facts
    line["compared"] = compared         # last: every number beside its limit
    return plain(line)


def run(args) -> int:
    from cpd_tpu.ops import require_tpu
    from cpd_tpu.utils import enable_compile_cache

    found = discover()[args.workload]
    chips = found["cell"]["chips"]
    devices = require_tpu("benchmark/run.py")
    if len(devices) < chips:
        print(f"benchmark/run.py: cell {args.workload!r} needs {chips} chips, "
              f"jax found {len(devices)}; refusing to run", file=sys.stderr)
        return 2

    import jax
    enable_compile_cache()
    # every program of the run, not only those that took over a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if args.trace:
        # the cache's key leaves metadata out, so a cache another checkout
        # warmed would serve that checkout's scope names
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    counter = CompileCounter().install()
    peaks = load_json(HERE, "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise KeyError(f"no peaks recorded for device kind {kind!r} in "
                       f"benchmark/peaks.json")

    line = drive(found, args, devices, peaks[kind], counter)
    print(json.dumps(line), flush=True)
    for name, pair in line["compared"].items():
        print(f"compared {name} {pair['value']!r} limit {pair['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", metavar="DIR",
                   help="with --trace 1, write the profiler's trace under "
                        "DIR and keep it (default: a temporary directory)")
    p.add_argument("--list", action="store_true",
                   help="print every cell with the files it resolves to")
    args = p.parse_args(argv)
    if args.list:
        print(json.dumps(discover(), indent=1, sort_keys=True))
        return 0
    if not args.workload:
        p.error("--workload is required")
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
