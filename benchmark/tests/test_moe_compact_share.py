"""`moe.compact_share` (PR 35): the share of expert layers whose `d`-wide
work ran over the static bound of `models/mla_moe.py:_row_bound` and not
over all T·k rows.  The metric's file resolves through `run.discover()`
for its one cell and reads the step's own counter; a tiny cell that holds
2 of 8 experts (768 pairs bounded by 512 rows, so its step holds the
`lax.cond`) goes through a whole run on the CPU, comes out `correct` and
reports 1; its compiled step, and the one of `test_mla_moe_cell.py`'s
stand-in that holds 4 of 8 (no bound below its pairs, no `cond`), carry
the three scopes the accepted `moe.*` metrics read.
"""

from __future__ import annotations

import importlib
import json
import os
import re

import jax
import pytest

import conftest
from benchmark import run, trace_scopes
from test_check import drive, failed
from test_mla_moe_cell import MOE_CONFIG, MOE_TRAFFIC, NEW_METRICS

CELL = "moonlight_16b_ep8_aps_e5m2_1chip"
METRIC = "moe.compact_share"
BOUNDED = {**MOE_CONFIG, "n_routed_experts": 2}


def test_metric_resolves_for_its_cell_and_reads_the_counter():
    with open(os.path.join(conftest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == METRIC)
    assert entry == {
        "name": METRIC, "unit": "share", "better": "higher",
        "source": "program_counter",
        "layer": "expert routing (`models/mla_moe.py:RoutedExperts`)",
        "moves": "train_rate_per_chip", "workloads": [CELL]}
    assert bench["per_layer"][-1] == entry      # appended, nothing moved
    found = run.discover()
    spec = found[CELL]["metrics"]["per_layer"][METRIC]
    assert (spec["reader"], spec["args"]) == ("counts:step_metric",
                                              {"key": "moe_compact"})
    read = run.resolve(spec["reader"], "readers")
    assert read({"last_metrics": {"moe_compact": 1.0}}, spec["args"]) == 1.0
    # a program without the counter (the parent): nothing, and no raise
    assert read({"last_metrics": {"moe_pairs_held": 7.0}},
                spec["args"]) is None
    assert read({}, spec["args"]) is None
    for name, cell in found.items():
        assert (METRIC in cell["metrics"]["per_layer"]) == (name == CELL)
    # the cell bounds its rows: 8 of 64 experts held, 98,304 pairs
    from cpd_tpu.models.mla_moe import _row_bound
    config, traffic = found[CELL]["config"], found[CELL]["traffic"]
    pairs = (traffic["batch_per_chip"] * traffic["seq_len"]
             * config["num_experts_per_tok"])
    assert _row_bound(pairs, config["n_routed_experts"],
                      config["n_routed_experts_published"]) == 24576 < pairs


def test_bounded_tiny_cell_is_correct_and_reports_the_compact_path():
    line = drive(BOUNDED, MOE_TRAFFIC)
    facts = line["facts"]
    assert line["correct"], (failed(line), line["compared"])
    assert set(facts["step_compiled"].values()) == {0}
    assert facts["last_metrics"]["moe_compact"] == 1.0
    assert 0 < facts["last_metrics"]["moe_pairs_held"] <= 512


@pytest.mark.parametrize("held", [2, 4])
def test_compiled_step_keeps_the_scopes_the_moe_metrics_read(held):
    from cpd_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    runner = importlib.import_module(
        "benchmark.runners.train_mla_moe_lm").build(
            {**MOE_CONFIG, "n_routed_experts": held}, MOE_TRAFFIC, mesh,
            None)
    key = jax.ShapeDtypeStruct((2,), "uint32")
    state = jax.eval_shape(runner.init_state, key)
    a, b = jax.eval_shape(runner.make_batch, key)
    text = jax.jit(runner.step).lower(state, a, b).compile().as_text()
    paths = {trace_scopes.scope_path(n)
             for n in re.findall(r'op_name="([^"]+)"', text)}
    for scope in ("cpd.moe_dispatch", "cpd.moe_experts", "cpd.moe_combine"):
        assert f"cpd.loss_grad/{scope}" in paths, sorted(paths)
    for metric in ("moe.route_ms_per_step", "moe.experts_ms_per_step",
                   "moe.experts_roofline_pct"):
        include = re.compile(run.load_json(
            run.HERE, "metrics", metric + ".json")["args"]["include"])
        yes, no = NEW_METRICS[metric]
        assert all(include.search(p) for p in yes if p in paths)
        assert not any(include.search(p) for p in no)
        assert any(include.search(p) for p in paths)
    # the branch's own names stay out of the paths: a scope is `cpd.*`
    assert not any("branch" in p or "cond" in p for p in paths)
