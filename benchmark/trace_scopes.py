"""From a `jax.profiler` trace to device time by the program's own layers.

`trace_reduce` names operations as XLA does (`fusion.14`, `kLoop`).  The
same trace also says which layer of the program each operation came
from: every operation's **event metadata** (not the event) carries the
stats `tf_op` (jax's name stack, e.g.
`jit(step_fn)/cpd.reduce/aps.max_exp/reduce_max`), `hlo_category`, `flops`,
`bytes_accessed` and `shape_with_layout`.  `jax.profiler.ProfileData`
exposes only an event's own stats, so `load_metadata` reads the two maps
off the `.xplane.pb` directly, with a wire-format decoder that descends
into `XPlane.event_metadata` and `XPlane.stat_metadata` and skips the
event lines as bytes.  The join key is the event's name (the whole HLO
line), which is the metadata's name.

The layer names are the scopes of `cpd_tpu/obs/scopes.py`: `cpd.*` and
`kernel.*` own an operation, `aps.*`, `wire.*` and `reduce.*` refine it.
`scope_path` turns a name stack into the path the metric files match with
regular expressions; no scope is named in this file, only the families.
A program without scopes (an older checkout, or an executable a compile
cache served with an older checkout's metadata) gives `scopes_found:
False`, and every reader then reports nothing, not zero.

    python -m benchmark.trace_scopes --workload CELL --trace-dir DIR \
        --line RESULT.json [--base-line RESULT0.json]

prints, for a trace kept with `run.py --trace 1 --keep-trace DIR` and the
result line of that run, the whole scope table and every `per_layer`
metric of `BENCHMARK.json` that a `readers/scopes.py` function reads for
the cell.  (`run.py` prints the same metrics itself: it hands
`load_scopes`'s table to the readers as `ctx["scopes"]` while the trace's
directory still exists, and the ten largest scopes as
`breakdown.device_scopes`.)
"""

from __future__ import annotations

import json
import re
import struct

from benchmark import trace_reduce

OWNER = re.compile(r"^(cpd|kernel)\.")
SCOPE = re.compile(r"^(cpd|kernel|aps|wire|reduce)\.")
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter)")
CONTAINER = re.compile(r"^(while|conditional|call)\b")
ASYNC_LINE = "Async XLA Ops"
UNSCOPED = "unscoped"
_STATS = {"tf_op": "scope", "flops": "flops",
          "bytes_accessed": "bytes_accessed", "hlo_category": "category",
          "shape_with_layout": "shape"}


# --------------------------------------------------------------- decoder

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a memoryview, not parsed further."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield field, wire, value


def _map_entry(buf):
    """A protobuf map entry: key = field 1, value = field 2."""
    key = value = None
    for field, _, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _stat(buf, stat_names):
    """One XStat -> (stat name, value); strings may be references into
    the plane's `stat_metadata`."""
    name = value = None
    for field, _, v in _fields(buf):
        if field == 1:
            name = stat_names.get(v)
        elif field == 2:
            value = struct.unpack("<d", v)[0]
        elif field in (3, 4):
            value = v - (1 << 64) if field == 4 and v >> 63 else v
        elif field in (5, 6):
            value = bytes(v).decode("utf-8", "replace")
        elif field == 7:
            value = stat_names.get(v, "")
    return name, value


def _plane(buf):
    """(plane name, {event name: {stat name: value}}) of one XPlane."""
    name, events, stat_names = "", [], {}
    for field, _, v in _fields(buf):
        if field == 2:
            name = bytes(v).decode()
        elif field == 4:
            events.append(_map_entry(v)[1])
        elif field == 5:
            sid, meta = _map_entry(v)
            for f, _, x in _fields(meta):
                if f == 2:
                    stat_names[sid] = bytes(x).decode()
    table = {}
    for meta in events:
        event_name, stats = "", {}
        for f, _, x in _fields(meta):
            if f == 2:
                event_name = bytes(x).decode("utf-8", "replace")
            elif f == 5:
                k, val = _stat(x, stat_names)
                stats[k] = val
        table[event_name] = stats
    return name, table


def load_metadata(path: str) -> dict:
    """{device: {hlo line: {"scope", "flops", "bytes_accessed",
    "category", "shape"}}} for every `/device:TPU:<n>` plane; a stat the
    trace does not carry is left out."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, _, v in _fields(space):
        if field != 1:
            continue
        name, table = _plane(v)
        m = trace_reduce.DEVICE_PLANE.match(name)
        if m:
            out[m.group(1)] = {
                text: {_STATS[k]: val for k, val in stats.items()
                       if k in _STATS}
                for text, stats in table.items()}
    return out


def load_async(path: str) -> dict:
    """{device: [[hlo line, start_ns, dur_ns], ...]} of the `Async XLA
    Ops` line: an async operation's span from its start to its done."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            out[m.group(1)] = [
                [e.name, e.start_ns, e.duration_ns]
                for line in plane.lines if line.name == ASYNC_LINE
                for e in line.events]
    return out


# ------------------------------------------------------------- reduction

def _innermost(component: str) -> str:
    """jax wraps a scope entered under a transformation in its marker:
    `transpose(jvp(cpd.reduce))` -> `cpd.reduce`."""
    return component.rsplit("(", 1)[-1].rstrip(")")


def scope_path(name_stack: str) -> str:
    """`jit(step)/cpd.loss_grad/transpose(jvp(Model))/kernel.k/mul` ->
    `cpd.loss_grad/kernel.k@bwd`: the scope components in order (repeats
    dropped), `@bwd` when jax's `transpose(` marker stands at or after
    the last owning `cpd.*` component.  `unscoped` when no `cpd.*` or
    `kernel.*` component owns the operation."""
    parts = (name_stack or "").split("/")
    names = [_innermost(p) for p in parts]
    if not any(OWNER.match(n) for n in names):
        return UNSCOPED
    path, last_cpd = [], 0
    for i, n in enumerate(names):
        if SCOPE.match(n) and (not path or path[-1] != n):
            path.append(n)
        if n.startswith("cpd."):
            last_cpd = i
    bwd = any("transpose(" in p for p in parts[last_cpd:])
    return "/".join(path) + ("@bwd" if bwd else "")


_SHAPE = re.compile(r"([a-z]+\d+[a-z0-9]*|pred)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s4": 0.5, "u4": 0.5}


def shape_bytes(shape: str) -> tuple:
    """(bytes, [element types]) of an HLO shape, tuples summed."""
    total, kinds = 0.0, []
    for kind, dims in _SHAPE.findall(shape or ""):
        width = _BYTES.get(kind) or int(re.search(r"\d+", kind).group()) / 8
        count = 1
        for d in filter(None, dims.split(",")):
            count *= int(d)
        total += width * count
        kinds.append(kind)
    return total, kinds


def received_bytes(name: str, shape: str, world: int) -> float:
    """Bytes one device receives in one run of a collective, from the
    executed operation's own shape: all-gather output x (W-1)/W,
    collective-permute output, all-reduce 2(W-1)/W x its bytes."""
    size, _ = shape_bytes(shape)
    if name.startswith("all-gather") or name.startswith("all-to-all"):
        return size * (world - 1) / world
    if name.startswith("all-reduce"):
        return 2.0 * size * (world - 1) / world
    if name.startswith("reduce-scatter"):
        return size * (world - 1)
    return size     # collective-permute


def exposed_ns(collectives, others) -> float:
    """Length of the union of `collectives` minus the union of `others`
    (lists of [start, end))."""
    exposed = 0.0
    busy = trace_reduce.union(others)
    j = 0
    for s, e in trace_reduce.union(collectives):
        while j < len(busy) and busy[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(busy) and busy[k][0] < e:
            exposed += max(busy[k][0] - at, 0.0)
            at = max(at, busy[k][1])
            k += 1
        exposed += max(e - at, 0.0)
    return exposed


def reduce_scopes(tables: dict, metadata: dict, async_ops=None) -> dict:
    """Device 0 of `tables` (what `trace_reduce.load` gives), inside the
    window `trace_reduce.window_of` gives, joined with `metadata`:

    - `by_scope`: {scope path: {"s", "flops", "bytes", "calls"}} with self
      times as `trace_reduce.self_times` computes them (a `while` is not
      counted twice), operations and bytes summed over every run of every
      operation that is not a container;
    - `busy_s` (their sum) and `unscoped_s`;
    - `collectives`: {"s", "exposed_s", "received_bytes", "dtypes",
      "calls"}: the collectives under a scope, the time during which
      nothing else ran on the device, the bytes a device received;
    - `scopes_found`: whether any operation had an owner at all."""
    lo, hi = trace_reduce.window_of(tables)
    dev0 = min(tables["devices"], key=int)
    world = len(tables["devices"])
    meta = metadata.get(dev0, {})
    ops0 = [e for e in tables["devices"][dev0]["ops"]
            if e[1] + e[2] > lo and e[1] < hi]
    calls: dict = {}
    for text, _, _ in ops0:
        calls[text] = calls.get(text, 0) + 1
    by_scope: dict = {}
    coll = {"s": 0.0, "received_bytes": 0.0, "dtypes": set(), "calls": 0}
    for text, t in trace_reduce.self_times(ops0).items():
        m = meta.get(text, {})
        path = scope_path(m.get("scope", ""))
        row = by_scope.setdefault(
            path, {"s": 0.0, "flops": 0.0, "bytes": 0.0, "calls": 0})
        row["s"] += t * 1e-9
        row["calls"] += calls[text]
        name = trace_reduce.op_name(text)
        if not CONTAINER.match(name):
            row["flops"] += calls[text] * float(m.get("flops") or 0)
            row["bytes"] += calls[text] * float(m.get("bytes_accessed") or 0)
        if COLLECTIVE.match(name) and path != UNSCOPED:
            coll["s"] += t * 1e-9
            if not name.split(".")[0].endswith("-start"):
                shape = m.get("shape") or text.split(" = ", 1)[-1]
                coll["received_bytes"] += calls[text] * received_bytes(
                    name, shape, world)
                coll["dtypes"].update(shape_bytes(shape)[1])
                coll["calls"] += calls[text]

    def is_coll(text):
        return bool(COLLECTIVE.match(trace_reduce.op_name(text)))

    apart = {text for text in calls if is_coll(text)
             or CONTAINER.match(trace_reduce.op_name(text))}
    spans = [[s, s + d] for text, s, d in ops0
             if text in apart and is_coll(text)]
    spans += [[s, s + d] for text, s, d in (async_ops or {}).get(dev0, [])
              if is_coll(text) and s + d > lo and s < hi]
    others = [[s, s + d] for text, s, d in ops0 if text not in apart]
    coll["exposed_s"] = exposed_ns(trace_reduce.clip(spans, lo, hi),
                                   trace_reduce.clip(others, lo, hi)) * 1e-9
    coll["dtypes"] = sorted(coll["dtypes"])
    return {
        "scopes_found": any(p != UNSCOPED for p in by_scope),
        "by_scope": by_scope,
        "busy_s": sum(r["s"] for r in by_scope.values()),
        "unscoped_s": by_scope.get(UNSCOPED, {"s": 0.0})["s"],
        "collectives": coll, "world": world,
    }


def load_scopes(trace_dir: str, tables: dict | None = None) -> dict:
    """Everything above for one kept trace directory."""
    path = trace_reduce.find_xplane(trace_dir)
    tables = tables or trace_reduce.load(path)
    return reduce_scopes(tables, load_metadata(path), load_async(path))


def top_scopes(scopes: dict, top: int = 10) -> list:
    """The `top` largest scope paths, seconds: the form of `device_ops`."""
    rows = sorted(scopes["by_scope"].items(), key=lambda kv: -kv[1]["s"])
    return [[path, row["s"]] for path, row in rows[:top]]


def main(argv=None) -> int:
    import argparse
    import types

    from benchmark import run

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--trace-dir", required=True,
                   help="what `run.py --trace 1 --keep-trace` was given")
    p.add_argument("--line", required=True,
                   help="file whose last line is that run's result line")
    p.add_argument("--base-line",
                   help="a `--trace 0` result line of the same cell: adds "
                        "`traced_rate`, the traced stretch's rate over it")
    args = p.parse_args(argv)

    def last_line(path):
        with open(path) as f:
            return json.loads(f.read().strip().splitlines()[-1])

    line = last_line(args.line)
    found = run.discover()[args.workload]
    scopes = load_scopes(args.trace_dir)
    steps = line["attempted"]
    ctx = {"scopes": scopes, "chips": found["cell"]["chips"],
           "window": types.SimpleNamespace(steps=steps),
           "config": found["config"], "traffic": found["traffic"],
           "peaks": run.load_json(run.HERE, "peaks.json")[
               line["device"]["kind"]]}
    metrics = {}
    for name, spec in found["metrics"]["per_layer"].items():
        if not spec["reader"].startswith("scopes:"):
            continue
        value = run.resolve(spec["reader"], "readers")(
            ctx, spec.get("args", {}))
        if value is not None:
            metrics[name] = {"value": value, "unit": spec["unit"]}
    facts = {"scopes_found": scopes["scopes_found"],
             "wire_dtypes": scopes["collectives"]["dtypes"],
             "collective_calls_per_step":
             scopes["collectives"]["calls"] / steps,
             "collective_ms_per_step":
             1e3 * scopes["collectives"]["s"] / steps,
             "busy_ms_per_step": 1e3 * scopes["busy_s"] / steps}
    if args.base_line:
        base = last_line(args.base_line)
        facts["traced_rate"] = ((steps / line["facts"]["window_s"])
                                / (base["attempted"]
                                   / base["facts"]["window_s"]))
    print(json.dumps({
        "workload": args.workload, "steps": steps, "metrics": metrics,
        "breakdown": {"device_scopes": top_scopes(scopes)},
        "facts": facts,
        "by_scope_per_step": {
            path: {"ms": 1e3 * row["s"] / steps,
                   "gbytes": row["bytes"] / steps / 1e9,
                   "gflop": row["flops"] / steps / 1e9,
                   "calls": row["calls"] / steps}
            for path, row in sorted(scopes["by_scope"].items(),
                                    key=lambda kv: -kv[1]["s"])}}))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
