"""The hybrid short-convolution / attention mixture-of-experts runner
(`train_conv_moe_lm`) through a whole run on the CPU (`run.drive`) at a
tiny size, as `test_looped_cell.py` does for its runner: the stated format
passes; the next format down, a state left as it was and half of the
batch left out each come out `correct: false` by the check that names the
fault, while the first loss still matches the reference.  And: the cell
and its four metrics are found and resolve, the metric files select the
scope paths a compiled step of the model carries, the configuration's
file keeps the catalog row's keys, the counts are the hand counts to the
digit, and the reference's gradient compiles for a described v5e beside
four copies of the parameters.
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

CELL = "lfm2_24b_ep8_aps_e5m2_1chip"
CONFIG = "lfm2_24b_a2b_ep8_d5.json"
LAYER = "conv-attention hybrid layers (`models/conv_moe.py`)"
CONV_CONFIG = {
    "runner": "train_conv_moe_lm", "item": "token", "model": "conv_moe_lm",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "vocab_size": 256, "num_hidden_layers": 3,
    "layer_types": ["conv", "full_attention", "conv"], "conv_L_cache": 3,
    "conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True,
    "num_dense_layers": 1, "moe_intermediate_size": 32, "num_experts": 4,
    "num_experts_published": 8, "expert_first": 0, "num_experts_per_tok": 4,
    "routed_scaling_factor": 1, "rope_parameters": {"rope_theta": 1000000},
    "norm_eps": 1e-5, "initializer_range": 0.02,
    "model_kwargs": {"attn_impl": "flash", "remat": True,
                     "dtype": "bfloat16"},
    "classes": 256,
    "optimizer": {"name": "sgd", "momentum": 0.9, "weight_decay": 0.0,
                  "lr": 0.01},
    "ops_per_item": "conv_moe_lm:train_flops_per_token",
    "reference": "conv_moe_lm:loss", "head_part": "embed",
    "init_loss_band": [0.8, 1.5], "reference_loss_rtol": 0.05,
}
# over the stated run the readings sit near the other LM cells' (e5m2's
# 0.053 and bf16 compute); sqrt(a b) against e5m1, as the traffic files'
CONV_TRAFFIC = {**tiny.LM_TRAFFIC, **tiny.limits(
    update_rel_err=0.075, update_rel_err_worst_part=0.08,
    update_rel_err_head=0.075, grad_norm_gap=0.15, change_norm_gap=0.15,
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
    "conv.mixer_ms_per_step": (
        ["cpd.loss_grad/cpd.conv_mixer", "cpd.loss_grad/cpd.conv_mixer@bwd"],
        ["cpd.loss_grad", "cpd.loss_grad/cpd.gqa_attn",
         "cpd.loss_grad/cpd.dense_mlp"]),
    "conv.mixer_roofline_pct": (
        ["cpd.loss_grad/cpd.conv_mixer"],
        ["cpd.loss_grad/cpd.gqa_attn", "cpd.loss_grad/cpd.moe_experts"]),
    "attn.gqa_ms_per_step": (
        ["cpd.loss_grad/cpd.gqa_attn", "cpd.loss_grad/cpd.gqa_attn@bwd",
         "cpd.loss_grad/cpd.gqa_attn/kernel.flash_gqa_fwd",
         "cpd.loss_grad/cpd.gqa_attn/kernel.flash_gqa_bwd_dq@bwd"],
        ["cpd.loss_grad", "cpd.loss_grad/cpd.conv_mixer",
         "cpd.loss_grad/cpd.moe_router"]),
    "kernel.flash_gqa64_fwd_roofline_pct": (
        ["cpd.loss_grad/cpd.gqa_attn/kernel.flash_gqa_fwd"],
        ["cpd.loss_grad/cpd.gqa_attn",
         "cpd.loss_grad/cpd.gqa_attn/kernel.flash_gqa_bwd_dq@bwd"]),
}


def bench() -> dict:
    with open(os.path.join(conftest.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def runner_of(config, traffic, mesh, reference=None):
    return importlib.import_module(
        f"benchmark.runners.{config['runner']}").build(config, traffic,
                                                       mesh, reference)


@pytest.mark.parametrize("fault", sorted(FAULTS_HERE))
def test_runner_through_the_check(fault):
    how, must_fail = FAULTS_HERE[fault]
    how = dict(how)
    traffic = {**CONV_TRAFFIC, "reduce": how.pop("reduce", tiny.REDUCE)}
    line = drive(CONV_CONFIG, traffic, **how)
    facts = line["facts"]
    assert line["correct"] == (fault == "none"), (failed(line),
                                                  line["compared"])
    assert must_fail <= failed(line), (fault, failed(line), line["compared"])
    assert facts["checks"]["matches_reference"]
    assert not failed(line) & PASSING, failed(line)
    assert set(facts["step_compiled"].values()) == {0}
    if fault == "none":
        assert set(facts["update_rel_err_by_part"]) == {
            "block0", "block1", "block2", "embed", "norm_f"}
        # the selection biases of the two expert layers: no gradient
        assert facts["leaves_left_out_of_change"] == 2
        # the step's counters reach the readers (`counts:step_metric`)
        pairs = facts["last_metrics"]["moe_pairs_held"]
        assert 0 < pairs < 2 * 2 * 128 * 4
        assert facts["last_metrics"]["moe_load_max_over_mean"] >= 1.0
        assert facts["last_metrics"]["moe_compact"] in (0.0, 1.0)


def test_a_file_that_says_what_the_model_does_not_compute_is_refused():
    from cpd_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="conv_bias"):
        runner_of({**CONV_CONFIG, "conv_bias": True}, CONV_TRAFFIC, mesh)


@pytest.fixture(scope="module")
def scope_paths():
    """The scope path of every operation of the tiny cell's compiled
    step, as `trace_scopes` reduces a device trace's `tf_op`s."""
    from cpd_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    runner = runner_of(CONV_CONFIG, CONV_TRAFFIC, mesh)
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
            CONV_CONFIG, CONV_TRAFFIC)
        assert ops > 0 and nbytes > 0


def test_the_cell_and_its_four_metrics_are_found():
    found, b = run.discover(), bench()
    mine = [m for m in b["per_layer"] if CELL in m.get("workloads", [])]
    assert sorted(m["name"] for m in mine) == sorted(SCOPE_METRICS)
    assert {m["layer"] for m in mine} == {LAYER, "kernels"}
    assert all(m["moves"] == "train_rate_per_chip"
               and m["workloads"] == [CELL] for m in mine)
    # `test_mla_moe_cell.py` counts the layers that start so
    assert not any(m["layer"].startswith("model layers") for m in mine)
    cell = found[CELL]
    assert cell["cell"]["chips"] == 1
    assert cell["config"]["runner"] == "train_conv_moe_lm"
    assert cell["traffic"]["batch_per_chip"] == 2
    assert cell["traffic"]["seq_len"] == 8192
    for m in mine:
        spec = cell["metrics"]["per_layer"][m["name"]]
        reader = run.resolve(spec["reader"], "readers")
        # an untraced run, or the parent's program: nothing, no raise
        assert reader({"scopes": None}, spec["args"]) is None
    # every metric without a list reads the new cell too
    for m in b["per_layer"]:
        if "workloads" not in m:
            assert m["name"] in cell["metrics"]["per_layer"]
    # the metrics that name other cells keep their lists
    old = [m for m in b["per_layer"] if "workloads" in m and m not in mine]
    assert old and not any(CELL in m["workloads"] for m in old)
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 2


def test_configuration_file_keeps_the_catalog_rows_keys():
    config = run.load_json(run.HERE, "configs", CONFIG)
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True}
    assert {k: config[k] for k in published} == published
    reduced = ["num_hidden_layers", "layer_types", "num_dense_layers",
               "num_experts", "vocab_size"]
    assert config["reduced"] == reduced
    assert set(config["reduced_detail"]) == set(reduced)
    assert config["layer_types"] == ["conv", "full_attention", "conv",
                                     "conv", "conv"]
    assert config["layer_types_published"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert len(config["layer_types_published"]) == 40
    assert config["layer_types_published"].count("full_attention") == 10
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 8192)
    assert (config["num_hidden_layers_published"],
            config["num_dense_layers_published"],
            config["num_experts_published"],
            config["vocab_size_published"]) == (40, 2, 64, 65536)
    assert config["expert_parallel"] == 8 and config["head_part"] == "embed"
    for key in ("tie_embedding", "initializer_range", "optimizer",
                "expert_bias", "gate_epsilon", "rope_pairing", "remat",
                "compute", "data"):
        assert config["assumed"][key]
    entry = next(c for c in bench()["configs"]
                 if c["file"].endswith(CONFIG))
    assert entry["source"].startswith(config["source"])
    assert entry["reduced"] == reduced


def test_param_count_by_hand_and_by_the_program():
    found = run.discover()[CELL]
    config = found["config"]
    from cpd_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    runner = runner_of(config, found["traffic"], mesh)
    state = jax.eval_shape(runner.init_state,
                           jax.ShapeDtypeStruct((2,), "uint32"))
    count = sum(l.size for l in jax.tree.leaves(state.params))
    d, ff, hd = 2048, 11776, 64
    conv = 3 * d * d + d * d + 3 * d
    attn = 32 * hd * d + 2 * 8 * hd * d + 32 * hd * d + 2 * hd
    dense = 3 * d * ff
    routed = d * 64 + 64 + 8 * 3 * d * 1536
    assert (conv, attn, dense, routed) == (
        16_783_360, 10_485_888, 72_351_744, 75_628_608)
    layers = [conv + dense, attn + routed, conv + routed, conv + routed,
              conv + routed]
    assert [x + 2 * d for x in layers[:3]] == [89_139_200, 86_118_592,
                                               92_416_064]
    assert count == sum(layers) + 5 * 2 * d + 8192 * d + d == 469_285_248
    assert sorted(state.params) == ["block0", "block1", "block2", "block3",
                                    "block4", "embed", "norm_f"]
    assert runner.items_per_step == 2 * 8192
    # 14 bytes a parameter: 6.57 GB
    assert round(14 * count / 1e9, 2) == 6.57


def test_flops_match_the_hand_count():
    from benchmark.flops import conv_moe_lm as flops
    config = run.load_json(run.HERE, "configs", CONFIG)
    traffic = {"batch_per_chip": 2, "seq_len": 8192}
    d = 2048
    conv, dense = 4 * 2 * 4 * d * d, 2 * 3 * d * 11776
    attn_proj, core = 2 * 10_485_760, 2 * 2 * 32 * 64 * 4096
    routed = 4 * 2 * (d * 64 + 4 * 8 / 64 * 3 * d * 1536)
    head = 2 * 8192 * d
    assert [round(x / 1e6, 1) for x in (conv, dense, attn_proj, core,
                                        routed, head)] == [
        134.2, 144.7, 21.0, 33.6, 38.8, 33.6]
    forward = flops.forward_flops_per_token(config, traffic)
    assert forward == pytest.approx(
        conv + dense + attn_proj + core + routed + head, rel=1e-12)
    assert round(forward / 1e6, 1) == 405.8
    assert flops.train_flops_per_token(config, traffic) == 3 * forward
    assert round(3 * forward * 2 * 8192 / 1e12, 2) == 19.95
    # the conv mixers: 4 layers x (forward, recomputation, 2 backward)
    ops, nbytes = flops.conv_mixer_all_passes(config, traffic)
    assert ops == 4 * 4 * 2 * 16384 * 4 * d * d
    assert nbytes == 4 * 4 * 2 * (4 * d * d + 16384 * 6 * d)
    # the forward kernel at heads of 64, both calls of the one layer
    ops, nbytes = flops.flash_fwd(config, traffic)
    assert ops == 2 * (2 * 2 * 2 * 32 * 8192 * 8192 * 64 / 2)
    assert nbytes == 2 * (2 * 2 * 8192 * 64 * (2 * 32 + 2 * 8)
                          + 4 * 2 * 32 * 8192)


def test_new_code_names_no_cell_configuration_or_metric():
    b = bench()
    names = ([c["name"] for c in b["configs"]]
             + [w["name"] for w in b["workloads"]]
             + [w["traffic"] for w in b["workloads"]]
             + [m["name"] for m in b["end_to_end"] + b["per_layer"]])
    for rel in ("runners/train_conv_moe_lm.py", "reference/conv_moe_lm.py",
                "flops/conv_moe_lm.py"):
        with open(os.path.join(run.HERE, rel)) as f:
            text = f.read()
        for name in names:
            assert not re.search(rf"(?<![\w.]){re.escape(name)}(?![\w.])",
                                 text), (rel, name)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(run.HERE, "reference", "conv_moe_lm.py")) as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+cpd_tpu", text, re.M)


def test_reference_gradient_compiles_beside_four_copies(topo, capsys):  # noqa: F811
    """`check.py` holds four trees of the parameters' size beside the
    reference's backward pass (its output one more): compiled for a
    described v5e at the cell's real size, all of it fits the chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cpd_tpu.parallel.mesh import make_mesh

    found = run.discover()[CELL]
    config, traffic = found["config"], found["traffic"]
    mesh = make_mesh(dp=1, devices=topo.devices[:1])
    runner = runner_of(config, traffic, mesh,
                       run.resolve(config["reference"], "reference"))

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
