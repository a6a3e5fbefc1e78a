"""The latent-attention mixture-of-experts runner (`train_mla_moe_lm`)
through a whole run on the CPU (`run.drive`) at a tiny size, as
`test_check.py` does for the two runners it knows: the stated format
passes; the next format down, a state left as it was and half of the
batch left out each come out `correct: false` by the check that names the
fault, while the first loss still matches the reference.  And: the new
metric files resolve, and select the scope paths a compiled step of the
model carries.
"""

from __future__ import annotations

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

MOE_CONFIG = {
    "runner": "train_mla_moe_lm", "item": "token", "model": "mla_moe_lm",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 128, "vocab_size": 256, "num_hidden_layers": 2,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "n_routed_experts_published": 8,
    "expert_first": 0, "num_experts_per_tok": 3, "n_shared_experts": 2,
    "routed_scaling_factor": 2.446, "rope_theta": 50000,
    "rms_norm_eps": 1e-5, "first_k_dense_replace": 1,
    "initializer_range": 0.02,
    "model_kwargs": {"attn_impl": "flash", "flash_bwd": "chunked",
                     "remat": True, "dtype": "bfloat16"},
    "classes": 256,
    "optimizer": {"name": "sgd", "momentum": 0.9, "weight_decay": 0.0,
                  "lr": 0.01},
    "ops_per_item": "mla_moe_lm:train_flops_per_token",
    "reference": "mla_moe_lm:loss", "head_part": "lm_head",
    "init_loss_band": [0.8, 1.5], "reference_loss_rtol": 0.05,
}
# over the stated run: all parameters 0.0536, the worst part (the expert
# block) 0.0576, the head 0.0524, norm gaps 0.006 and 0.005; e5m1 0.1044,
# 0.1125, 0.1051.  sqrt(a b), as the traffic files' limits
MOE_TRAFFIC = {**tiny.LM_TRAFFIC, **tiny.limits(
    update_rel_err=0.075, update_rel_err_worst_part=0.08,
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
NEW_METRICS = {
    "attn.mla_ms_per_step": (
        ["cpd.loss_grad/cpd.mla", "cpd.loss_grad/cpd.mla@bwd",
         "cpd.loss_grad/cpd.mla/kernel.flash_gqa_fwd"],
        ["cpd.loss_grad", "cpd.loss_grad/cpd.moe_shared"]),
    "moe.route_ms_per_step": (
        ["cpd.loss_grad/cpd.moe_router", "cpd.loss_grad/cpd.moe_dispatch",
         "cpd.loss_grad/cpd.moe_combine"],
        ["cpd.loss_grad/cpd.moe_experts", "cpd.loss_grad/cpd.moe_shared"]),
    "moe.experts_ms_per_step": (
        ["cpd.loss_grad/cpd.moe_experts"],
        ["cpd.loss_grad/cpd.moe_router", "cpd.loss_grad/cpd.dense_mlp"]),
    "moe.shared_ms_per_step": (
        ["cpd.loss_grad/cpd.moe_shared"],
        ["cpd.loss_grad/cpd.moe_experts", "cpd.loss_grad/cpd.dense_mlp"]),
    "moe.experts_roofline_pct": (
        ["cpd.loss_grad/cpd.moe_experts"], ["cpd.loss_grad/cpd.moe_shared"]),
    "kernel.flash_mla_fwd_roofline_pct": (
        ["cpd.loss_grad/cpd.mla/kernel.flash_gqa_fwd"],
        ["cpd.loss_grad/cpd.mla", "cpd.loss_grad/cpd.mla@bwd"]),
}


@pytest.mark.parametrize("fault", sorted(FAULTS_HERE))
def test_runner_through_the_check(fault):
    how, must_fail = FAULTS_HERE[fault]
    how = dict(how)
    traffic = {**MOE_TRAFFIC, "reduce": how.pop("reduce", tiny.REDUCE)}
    line = drive(MOE_CONFIG, traffic, **how)
    facts = line["facts"]
    assert line["correct"] == (fault == "none"), failed(line)
    assert must_fail <= failed(line), (fault, failed(line), line["compared"])
    assert facts["checks"]["matches_reference"]
    assert not failed(line) & PASSING, failed(line)
    assert set(facts["step_compiled"].values()) == {0}
    if fault == "none":
        assert set(facts["update_rel_err_by_part"]) == {
            "block0", "block1", "embed", "lm_head", "norm_f"}
        # the selection bias of the one expert layer: no gradient
        assert facts["leaves_left_out_of_change"] == 1
        # the step's counters reach the readers (`counts:step_metric`)
        pairs = facts["last_metrics"]["moe_pairs_held"]
        assert 0 < pairs < 2 * 128 * 3
        assert facts["last_metrics"]["moe_load_max_over_mean"] >= 1.0


@pytest.fixture(scope="module")
def scope_paths():
    """The scope path of every operation of the tiny cell's compiled
    step, as `trace_scopes` reduces a device trace's `tf_op`s."""
    import importlib

    from cpd_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    runner = importlib.import_module(
        "benchmark.runners.train_mla_moe_lm").build(
            MOE_CONFIG, MOE_TRAFFIC, mesh, None)
    key = jax.ShapeDtypeStruct((2,), "uint32")
    state = jax.eval_shape(runner.init_state, key)
    a, b = jax.eval_shape(runner.make_batch, key)
    text = jax.jit(runner.step).lower(state, a, b).compile().as_text()
    return {trace_scopes.scope_path(n)
            for n in re.findall(r'op_name="([^"]+)"', text)}


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_metric_files_select_their_scopes(metric, scope_paths):
    spec = run.load_json(run.HERE, "metrics", metric + ".json")
    yes, no = NEW_METRICS[metric]
    include = re.compile(spec["args"]["include"])
    assert all(include.search(p) for p in yes)
    assert not any(include.search(p) for p in no)
    # ... and the compiled step carries a path the metric reads
    assert any(include.search(p) for p in scope_paths), sorted(scope_paths)
    assert spec["reader"] in ("scopes:ms_per_step", "scopes:roofline_pct")
    if "ops" in spec["args"]:
        ops, nbytes = run.resolve(spec["args"]["ops"], "flops")(
            MOE_CONFIG, MOE_TRAFFIC)
        assert ops > 0 and nbytes > 0


def test_counter_metrics_and_the_cells_resolve():
    found = run.discover()
    with open(os.path.join(conftest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = [m for m in bench["per_layer"]
           if m["layer"].startswith("model layers")]
    assert len(new) == 8
    cell = found[new[0]["workloads"][0]]
    assert cell["config"]["runner"] == "train_mla_moe_lm"
    for m in new:
        assert m["name"] in cell["metrics"]["per_layer"]
    for name in ("moe.pairs_held_per_step", "moe.load_max_over_mean"):
        spec = cell["metrics"]["per_layer"][name]
        value = run.resolve(spec["reader"], "readers")(
            {"last_metrics": {spec["args"]["key"]: 7.0}}, spec["args"])
        assert value == 7.0
    # the stochastic cell differs from its twin in the rounding alone
    sr = found["resnet50_sr_e5m2_1chip"]["traffic"]
    twin = found["resnet50_aps_e5m2_1chip"]["traffic"]
    assert sr["reduce"] == {**twin["reduce"], "grad_rounding": "stochastic"}
    assert {k: sr[k] for k in ("batch_per_chip", "group")} == {
        k: twin[k] for k in ("batch_per_chip", "group")}


def test_flops_match_the_issues_count():
    from benchmark.flops import mla_moe_lm as flops
    config = run.load_json(run.HERE, "configs",
                           "moonlight_16b_a3b_ep8_d5.json")
    traffic = {"batch_per_chip": 2, "seq_len": 8192}
    assert flops.matmul_params(config) == 275_644_416
    per_token = flops.train_flops_per_token(config, traffic)
    assert per_token == 6 * 275_644_416 + 3 * 5 * 8192 * 16 * (192 + 128)
    assert flops.pairs_held_per_layer(config, traffic) == 12_288
    ops, _ = flops.flash_fwd(config, traffic)
    assert ops == 10 * 2 * 2 * 16 * 8192 * 8192 * 320 / 2    # remat: 2 calls
    ops, _ = flops.experts_all_passes(config, traffic)
    assert ops == 4 * 4 * 2 * 3 * 2048 * 1408 * 12_288


def test_new_code_names_no_cell_configuration_or_metric():
    with open(os.path.join(conftest.ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    names = ([c["name"] for c in b["configs"]]
             + [w["name"] for w in b["workloads"]]
             + [w["traffic"] for w in b["workloads"]]
             + [m["name"] for m in b["end_to_end"] + b["per_layer"]])
    for rel in ("runners/train_mla_moe_lm.py", "reference/mla_moe_lm.py",
                "flops/mla_moe_lm.py"):
        with open(os.path.join(run.HERE, rel)) as f:
            text = f.read()
        for name in names:
            assert not re.search(rf"(?<![\w.]){re.escape(name)}(?![\w.])",
                                 text), (rel, name)
