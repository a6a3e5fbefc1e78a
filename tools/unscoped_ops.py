"""List the operations of a kept device trace that no scope owns.

`step.unscoped_pct` says how much of the device's time the program's
scopes (`cpd_tpu/obs/scopes.py`) fail to name; this says which operations
those are, with the benchmark's own reader, so that the next reader or the
next scope can be aimed.  Keep a trace with the benchmark
(`python benchmark/run.py --workload <cell> --seed <n> --seconds 30 --trace
1 --keep-trace DIR`), then from the same checkout:

    python tools/unscoped_ops.py DIR STEPS [ROWS]

STEPS is the number of traced steps (`attempted` in the run's line), ROWS
how many groups to print (default 40).  Operations are
grouped by their HLO text with the numbers of names taken out, so that
one row is "this copy of this shape in this layout", all its instances;
the tail of each row is what is left of the operation's name stack.
"""

from __future__ import annotations

import os
import re
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

_NUMBER = re.compile(r"(%[A-Za-z_-]+(?:\.[A-Za-z_-]+)*)[.\d]*")


def main(argv) -> int:
    from benchmark import trace_reduce, trace_scopes

    trace_dir, steps = argv[0], int(argv[1])
    rows_wanted = int(argv[2]) if len(argv) > 2 else 40
    path = trace_reduce.find_xplane(trace_dir)
    tables = trace_reduce.load(path)
    lo, hi = trace_reduce.window_of(tables)
    dev0 = min(tables["devices"], key=int)
    meta = trace_scopes.load_metadata(path).get(dev0, {})
    ops0 = [e for e in tables["devices"][dev0]["ops"]
            if e[1] + e[2] > lo and e[1] < hi]
    calls: dict = {}
    for text, _, _ in ops0:
        calls[text] = calls.get(text, 0) + 1
    groups: dict = {}
    for text, t in trace_reduce.self_times(ops0).items():
        m = meta.get(text, {})
        if trace_scopes.scope_path(m.get("scope", "")) \
                != trace_scopes.UNSCOPED:
            continue
        key = _NUMBER.sub(r"\1", text)[:150]
        row = groups.setdefault(key, [0.0, 0, (m.get("scope") or "")[-80:]])
        row[0] += t * 1e-6 / steps
        row[1] += calls[text]
    total = sum(r[0] for r in groups.values())
    print(f"unscoped: {total:.3f} ms a step in {len(groups)} groups")
    for key, (ms, n, stack) in sorted(groups.items(),
                                      key=lambda kv: -kv[1][0])[:rows_wanted]:
        print(f"{ms:8.3f} ms {n / steps:7.1f} calls  {key}  || {stack}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
