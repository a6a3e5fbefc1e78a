"""The looped-LM runner (`train_looped_lm`) through a whole run on the CPU
(`run.drive`) at a tiny size, as `test_mla_moe_cell.py` does for its
runner: the stated format passes; the next format down, a state left as
it was and half of the batch left out each come out `correct: false` by
the check that names the fault, while the first loss still matches the
reference.  And: the new metric files resolve and select the scope paths
a compiled step of the model carries; the configuration's file keeps the
catalog row's keys; the counts are the issue's; the ring cell's traffic
is its gather twin's but for the transport; the reference's gradient
compiles for a described v5e beside four copies of the parameters.
"""

from __future__ import annotations

import importlib
import json
import os
import re

import jax
import pytest

import conftest
import tiny
from benchmark import run, trace_scopes
from benchmark.readings import FAULTS
from test_check import PASSING, UPDATE, drive, failed
from test_compile_v5e import HBM_BYTES, topo  # noqa: F401  (a fixture)

CELL = "ouro_2_6b_loop4_aps_e5m2_1chip"
RING_CELL = "resnet50_ring_e5m2_4chip"
LOOP_CONFIG = {
    "runner": "train_looped_lm", "item": "token", "model": "looped_lm",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 128, "vocab_size": 256, "num_hidden_layers": 2,
    "total_ut_steps": 4, "rope_theta": 1000000, "rms_norm_eps": 1e-6,
    "initializer_range": 0.02, "exit_entropy_beta": 0.1,
    "model_kwargs": {"attn_impl": "flash", "remat": True,
                     "dtype": "bfloat16"},
    "classes": 256,
    "optimizer": {"name": "sgd", "momentum": 0.9, "weight_decay": 0.0,
                  "lr": 0.01},
    "ops_per_item": "looped_lm:train_flops_per_token",
    "reference": "looped_lm:loss", "head_part": "lm_head",
    "init_loss_band": [0.8, 1.5], "reference_loss_rtol": 0.05,
}
# over the stated run: all parameters 0.054, the worst part (the gate,
# one small leaf and a bias) 0.067, the head 0.053, norm gaps 0.03;
# e5m1 0.104, 0.12, 0.104.  sqrt(a b), as the traffic files' limits
LOOP_TRAFFIC = {**tiny.LM_TRAFFIC, **tiny.limits(
    update_rel_err=0.075, update_rel_err_worst_part=0.09,
    update_rel_err_head=0.074, grad_norm_gap=0.15, change_norm_gap=0.15,
    loss_gap=0.005)}
NORMS = {"gradient_norms_match_reference", "change_norms_match_reference"}
FAULTS_HERE = {
    "none": ({}, set()),
    "next_format_down": (dict(reduce={**tiny.REDUCE, "grad_man": 1}),
                         UPDATE),
    "state_unchanged": (dict(break_step=FAULTS["unchanged"]),
                        UPDATE | NORMS),
    "half_of_the_batch": (dict(break_step=FAULTS["half_batch"]),
                          UPDATE | NORMS),
}
SCOPE_METRICS = {
    "loop.attn_ms_per_step": (
        ["cpd.loss_grad/cpd.loop_attn", "cpd.loss_grad/cpd.loop_attn@bwd",
         "cpd.loss_grad/cpd.loop_attn/kernel.flash_gqa_fwd",
         "cpd.loss_grad/cpd.loop_attn/kernel.flash_gqa_bwd_dq@bwd"],
        ["cpd.loss_grad", "cpd.loss_grad/cpd.loop_mlp",
         "cpd.loss_grad/cpd.loop_exit"]),
    "loop.mlp_ms_per_step": (
        ["cpd.loss_grad/cpd.loop_mlp"],
        ["cpd.loss_grad/cpd.loop_attn", "cpd.loss_grad/cpd.loop_exit"]),
    "loop.exit_ms_per_step": (
        ["cpd.loss_grad/cpd.loop_exit"],
        ["cpd.loss_grad/cpd.loop_attn", "cpd.loss_grad/cpd.loop_mlp",
         "cpd.loss_grad"]),
    "kernel.flash_mha_fwd_roofline_pct": (
        ["cpd.loss_grad/cpd.loop_attn/kernel.flash_gqa_fwd"],
        ["cpd.loss_grad/cpd.loop_attn",
         "cpd.loss_grad/cpd.loop_attn/kernel.flash_gqa_bwd_dq@bwd"]),
    "kernel.flash_mha_bwd_ms_per_step": (
        ["cpd.loss_grad/cpd.loop_attn/kernel.flash_gqa_bwd_dq@bwd",
         "cpd.loss_grad/cpd.loop_attn/kernel.flash_gqa_bwd_dkv"],
        ["cpd.loss_grad/cpd.loop_attn/kernel.flash_gqa_fwd",
         "cpd.loss_grad/cpd.loop_attn"]),
}


def bench() -> dict:
    with open(os.path.join(conftest.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("fault", sorted(FAULTS_HERE))
def test_runner_through_the_check(fault):
    how, must_fail = FAULTS_HERE[fault]
    how = dict(how)
    traffic = {**LOOP_TRAFFIC, "reduce": how.pop("reduce", tiny.REDUCE)}
    line = drive(LOOP_CONFIG, traffic, **how)
    facts = line["facts"]
    assert line["correct"] == (fault == "none"), (failed(line),
                                                  line["compared"])
    assert must_fail <= failed(line), (fault, failed(line), line["compared"])
    assert facts["checks"]["matches_reference"]
    assert not failed(line) & PASSING, failed(line)
    assert set(facts["step_compiled"].values()) == {0}
    if fault == "none":
        assert set(facts["update_rel_err_by_part"]) == {
            "block0", "block1", "embed", "exit_gate", "final_norm",
            "lm_head"}
        assert facts["leaves_left_out_of_change"] == 0
        # the step's counters reach the readers (`counts:step_metric`):
        # seeded gates are near zero, so the exits are near (1/2, 1/4,
        # 1/8, 1/8)
        assert 1.5 < facts["last_metrics"]["loop_expected_exit"] < 2.5
        assert 0.05 < facts["last_metrics"]["loop_last_exit_mass"] < 0.3


@pytest.fixture(scope="module")
def scope_paths():
    """The scope path of every operation of the tiny cell's compiled
    step, as `trace_scopes` reduces a device trace's `tf_op`s."""
    from cpd_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    runner = importlib.import_module(
        "benchmark.runners.train_looped_lm").build(
            LOOP_CONFIG, LOOP_TRAFFIC, mesh, None)
    key = jax.ShapeDtypeStruct((2,), "uint32")
    state = jax.eval_shape(runner.init_state, key)
    a, b = jax.eval_shape(runner.make_batch, key)
    text = jax.jit(runner.step).lower(state, a, b).compile().as_text()
    return {trace_scopes.scope_path(n)
            for n in re.findall(r'op_name="([^"]+)"', text)}


@pytest.mark.parametrize("metric", sorted(SCOPE_METRICS))
def test_new_metric_files_select_their_scopes(metric, scope_paths):
    spec = run.load_json(run.HERE, "metrics", metric + ".json")
    yes, no = SCOPE_METRICS[metric]
    include = re.compile(spec["args"]["include"])
    assert all(include.search(p) for p in yes)
    assert not any(include.search(p) for p in no)
    # ... and the compiled step carries a path the metric reads
    assert any(include.search(p) for p in scope_paths), sorted(scope_paths)
    assert spec["reader"] in ("scopes:ms_per_step", "scopes:roofline_pct")
    if "ops" in spec["args"]:
        ops, nbytes = run.resolve(spec["args"]["ops"], "flops")(
            LOOP_CONFIG, LOOP_TRAFFIC)
        assert ops > 0 and nbytes > 0


def test_metrics_counters_and_both_cells_resolve():
    found, b = run.discover(), bench()
    mine = [m for m in b["per_layer"] if CELL in m.get("workloads", [])]
    assert len(mine) == 7
    layers = {m["layer"] for m in mine}
    assert layers == {"looped model layers (`models/looped.py`)", "kernels"}
    cell = found[CELL]
    assert cell["config"]["runner"] == "train_looped_lm"
    assert cell["cell"]["chips"] == 1
    for m in mine:
        assert m["name"] in cell["metrics"]["per_layer"]
    for name, key in (("loop.expected_exit_step", "loop_expected_exit"),
                      ("loop.last_exit_mass", "loop_last_exit_mass")):
        spec = cell["metrics"]["per_layer"][name]
        assert spec["args"] == {"key": key}
        assert run.resolve(spec["reader"], "readers")(
            {"last_metrics": {key: 1.875}}, spec["args"]) == 1.875
        # a program without the counter (the parent): nothing, no raise
        assert run.resolve(spec["reader"], "readers")(
            {"last_metrics": {"loss": 1.0}}, spec["args"]) is None
    # the accepted backward-kernel metric keeps its list
    old = next(m for m in b["per_layer"]
               if m["name"] == "kernel.flash_gqa_bwd_ms_per_step")
    assert CELL not in old["workloads"]
    # every metric without a list reads both new cells
    for name in (CELL, RING_CELL):
        for m in b["per_layer"]:
            if "workloads" not in m:
                assert m["name"] in found[name]["metrics"]["per_layer"]


def test_ring_cell_is_its_gather_twin_but_for_the_transport():
    found, b = run.discover(), bench()
    ring, twin = found[RING_CELL], found["resnet50_aps_e5m2_4chip"]
    assert ring["cell"]["chips"] == 4 and ring["config"] == twin["config"]
    assert ring["traffic"]["reduce"] == {**twin["traffic"]["reduce"],
                                         "mode": "ring"}
    assert {k: ring["traffic"][k] for k in ("batch_per_chip", "group")} == {
        k: twin["traffic"][k] for k in ("batch_per_chip", "group")}
    hop = [m for m in b["per_layer"] if m.get("workloads") == [RING_CELL]]
    assert [m["name"] for m in hop] == ["ring.hop_ms_per_step"]
    include = re.compile(ring["metrics"]["per_layer"][
        "ring.hop_ms_per_step"]["args"]["include"])
    assert include.search("cpd.reduce/wire.collective/kernel.wire_hop")
    assert not include.search("cpd.reduce/wire.collective")
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 2
    # the wire's count follows the transport
    from benchmark.readers import counts
    ctx = {"traffic": ring["traffic"], "param_count": 25_557_032, "chips": 4}
    gather = counts.wire_bytes_per_step(
        {**ctx, "traffic": twin["traffic"]}, {})
    assert 0 < counts.wire_bytes_per_step(ctx, {}) < gather


def test_configuration_file_keeps_the_catalog_rows_keys():
    config = run.load_json(run.HERE, "configs", "ouro_2_6b_loop4.json")
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == ["full_attention"] * 48
    assert config["reduced"] == ["num_hidden_layers"]
    assert 4 <= config["num_hidden_layers"] <= 8
    assert config["num_hidden_layers_published"] == 48
    assert set(config["reduced_detail"]) == set(config["reduced"])
    for key in ("sandwich_norms", "exit_gate", "loss", "attention_bias",
                "rope_pairing", "optimizer", "initializer_range",
                "sequence_length", "data"):
        assert config["assumed"][key]
    entry = next(c for c in bench()["configs"]
                 if c["name"] == "ouro_2_6b_loop4")
    assert entry["source"].startswith(config["source"])
    assert "of its 48 layers" in entry["source"]
    assert "all 4 passes" in entry["source"]


def test_param_count_by_hand_and_by_the_program():
    found = run.discover()[CELL]
    config, layers = found["config"], found["config"]["num_hidden_layers"]
    from cpd_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    runner = importlib.import_module(
        f"benchmark.runners.{config['runner']}").build(
            config, found["traffic"], mesh, None)
    state = jax.eval_shape(runner.init_state,
                           jax.ShapeDtypeStruct((2,), "uint32"))
    count = sum(l.size for l in jax.tree.leaves(state.params))
    d, ff, v = 2048, 5632, 49152
    layer = 4 * d * d + 3 * d * ff + 4 * d
    assert layer == 51_388_416
    assert count == layers * layer + 2 * v * d + d + (d + 1)
    assert {7: 561_049_601}.get(layers, count) == count
    assert sorted(state.params) == sorted(
        [f"block{i}" for i in range(layers)]
        + ["embed", "exit_gate", "final_norm", "lm_head"])
    assert runner.items_per_step == 2 * 4096


def test_flops_match_the_issues_count():
    from benchmark.flops import looped_lm as flops
    config = {**run.load_json(run.HERE, "configs", "ouro_2_6b_loop4.json"),
              "num_hidden_layers": 7}
    traffic = {"batch_per_chip": 2, "seq_len": 4096}
    assert flops.matmul_params(config) == (
        4 * 7 * 51_380_224 + 4 * 100_663_296)
    per_token = flops.train_flops_per_token(config, traffic)
    assert per_token == 11_047_796_736 + 1_409_286_144
    assert round(per_token * 8192 / 1e12, 2) == 102.05
    ops, nbytes = flops.flash_fwd(config, traffic)
    one_call = 2 * 2 * 2 * 16 * 4096 * 4096 * 128 / 2
    assert round(one_call / 1e12, 3) == 0.137
    assert ops == 2 * 28 * one_call          # remat: both calls of a layer
    assert nbytes == 2 * 28 * (2 * 2 * 4096 * 128 * 64 + 4 * 2 * 16 * 4096)
    # the head's share of the matrix work, cut and uncut (the cell's why)
    head = lambda layers: 4 * 100_663_296 / (
        4 * layers * 51_380_224 + 4 * 100_663_296)
    assert round(100 * head(7), 1) == 21.9
    assert round(100 * head(6), 1) == 24.6
    assert round(100 * head(48), 1) == 3.9


def test_new_code_names_no_cell_configuration_or_metric():
    b = bench()
    names = ([c["name"] for c in b["configs"]]
             + [w["name"] for w in b["workloads"]]
             + [w["traffic"] for w in b["workloads"]]
             + [m["name"] for m in b["end_to_end"] + b["per_layer"]])
    for rel in ("runners/train_looped_lm.py", "reference/looped_lm.py",
                "flops/looped_lm.py"):
        with open(os.path.join(run.HERE, rel)) as f:
            text = f.read()
        for name in names:
            assert not re.search(rf"(?<![\w.]){re.escape(name)}(?![\w.])",
                                 text), (rel, name)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(run.HERE, "reference", "looped_lm.py")) as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+(cpd_tpu|benchmark)", text,
                         re.M)


def test_reference_gradient_compiles_beside_four_copies(topo, capsys):  # noqa: F811
    """`check.py` holds four trees of the parameters' size beside the
    reference's backward pass (its output one more): compiled for a
    described v5e at the cell's real size, all of it fits the chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cpd_tpu.parallel.mesh import make_mesh

    found = run.discover()[CELL]
    config, traffic = found["config"], found["traffic"]
    mesh = make_mesh(dp=1, devices=topo.devices[:1])
    runner = importlib.import_module(
        f"benchmark.runners.{config['runner']}").build(
            config, traffic, mesh, run.resolve(config["reference"],
                                               "reference"))

    def shaped(tree, spec):
        return jax.tree.map(lambda l: jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=NamedSharding(mesh, spec)), tree)

    key = jax.ShapeDtypeStruct((2,), "uint32")
    params = shaped(jax.eval_shape(runner.init_state, key), P()).params
    a, b = shaped(jax.eval_shape(runner.make_batch, key), P("dp"))
    m = jax.jit(runner.reference_grad).lower(
        params, a, b).compile().memory_analysis()
    copies = 4 * 4 * sum(l.size for l in jax.tree.leaves(params))
    total = copies + m.output_size_in_bytes + m.temp_size_in_bytes
    with capsys.disabled():
        print(f"\n{CELL}: the reference's gradient: outputs "
              f"{m.output_size_in_bytes / 2**30:.2f} GiB, temporaries "
              f"{m.temp_size_in_bytes / 2**30:.2f}; with four copies of the "
              f"parameters ({copies / 2**30:.2f}) {total / 2**30:.2f} GiB")
    assert total < HBM_BYTES - 2 ** 30       # and 1 GiB to spare
