"""A later PR adds a configuration, a cell and a metric as files and
entries of BENCHMARK.json, and edits no code: drop such files into a copy
of the benchmark and see `run.py --list` resolve them.  Also: what
BENCHMARK.json may hold, by the driver's rules."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import conftest
from benchmark import check

ROOT = conftest.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_new_files_are_found_with_no_code_edited(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = bench()
    base = tmp_path / "benchmark"
    limits = {}
    for name in check.CHECKS:        # every reading: a limit, or null and why
        limits[name + "_max"] = 0.05 if name == "update_rel_err" else None
        limits[name + "_why"] = "drop-in"
    config = json.loads((base / "configs" / "starcoder2_3b_d4.json")
                        .read_text())
    config["num_hidden_layers"] = 2
    (base / "configs" / "another_lm.json").write_text(json.dumps(config))
    (base / "traffic" / "fp32_1x2048.json").write_text(json.dumps(
        {"batch_per_chip": 1, "seq_len": 2048, "group": 2,
         "reduce": {"use_aps": False, "mode": "fast"},
         **limits}))
    (base / "metrics" / "step.host_ms_p50.json").write_text(json.dumps(
        {"reader": "window:step_ms_percentile", "args": {"q": 50}}))
    b["configs"].append({"name": "another_lm", "source": "https://x.example",
                         "file": "benchmark/configs/another_lm.json",
                         "reduced": ["num_hidden_layers"], "why": "drop-in"})
    b["workloads"].append({"name": "another_lm_fp32_1chip",
                           "config": "another_lm", "traffic": "fp32_1x2048",
                           "chips": 1, "why": "drop-in"})
    b["per_layer"].append({"name": "step.host_ms_p50", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "step builders", "moves": "step_ms_p90",
                           "workloads": ["another_lm_fp32_1chip"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    out = subprocess.run(
        [sys.executable, str(base / "run.py"), "--list"], check=True,
        capture_output=True, text=True, timeout=120).stdout
    cells = json.loads(out)
    new = cells["another_lm_fp32_1chip"]
    assert new["config"]["num_hidden_layers"] == 2
    assert new["traffic"]["seq_len"] == 2048
    assert new["metrics"]["per_layer"]["step.host_ms_p50"]["args"] == {"q": 50}
    old = [c for c in cells if c != "another_lm_fp32_1chip"]
    assert old and all("step.host_ms_p50" not in cells[c]["metrics"]
                       ["per_layer"] for c in old)

    # a traffic file says what each reading of its cell's first updates
    # is held to: one left out, or null with no reason, is refused
    for lacking, named in (
            ({k: v for k, v in limits.items() if k != "loss_gap_max"},
             "loss_gap_max"),
            ({**limits, "grad_norm_gap_why": ""}, "grad_norm_gap_why")):
        (base / "traffic" / "fp32_1x2048.json").write_text(json.dumps(
            {"batch_per_chip": 1, "seq_len": 2048, "group": 2,
             "reduce": {"use_aps": False, "mode": "fast"}, **lacking}))
        failed = subprocess.run(
            [sys.executable, str(base / "run.py"), "--list"],
            capture_output=True, text=True, timeout=120)
        assert failed.returncode != 0 and named in failed.stderr

    # a name that resolves to no file is an error, not a skipped cell
    b["workloads"][-1]["traffic"] = "no_such_traffic"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    failed = subprocess.run([sys.executable, str(base / "run.py"), "--list"],
                            capture_output=True, text=True, timeout=120)
    assert failed.returncode != 0 and "no_such_traffic" in failed.stderr


def test_code_names_no_cell_configuration_or_metric():
    b = bench()
    names = ([c["name"] for c in b["configs"]]
             + [w["name"] for w in b["workloads"]]
             + [w["traffic"] for w in b["workloads"]]
             + [m["name"] for m in b["end_to_end"] + b["per_layer"]])
    code = ["run.py", "loop.py", "check.py", "readings.py",
            "trace_reduce.py", "trace_scopes.py", "reference/sgd.py",
            "runners/base.py", "runners/train_lm.py",
            "runners/train_vision.py"]
    for rel in code:
        with open(os.path.join(ROOT, "benchmark", rel)) as f:
            text = f.read()
        for name in names:
            assert not re.search(rf"(?<![\w.]){re.escape(name)}(?![\w.])",
                                 text), (rel, name)


def test_benchmark_json_keeps_to_the_drivers_rules():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert len(json.dumps(b)) < 64 * 1024
    configs = {c["name"] for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f).get("reduced", []) == c["reduced"]
    assert configs == {w["config"] for w in b["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs) == len(cells)
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(cells) // 4)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert "\t" not in w["why"] and "\n" not in w["why"]

    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    reported = {}                      # end-to-end metric -> its cells
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        reported[m["name"]] = set(m.get("workloads", cells))
    assert "setup_s" in reported
    layers = set()
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert 1 <= len(m["layer"]) <= 200
        layers.add(m["layer"])
        # `moves` is reported in every cell where this metric is
        assert set(m.get("workloads", cells)) <= reported[m["moves"]]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
        assert m.get("workloads") != []         # a list names a cell
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           m["name"] + ".json"))
    for name in cells:
        mine_e = [m for m in b["end_to_end"]
                  if name in m.get("workloads", cells)]
        mine_l = [m for m in b["per_layer"]
                  if name in m.get("workloads", cells)]
        assert len(mine_e) >= 2 and mine_l

    # every file under `paths` is named from a name's characters and "/"
    for path in b["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
