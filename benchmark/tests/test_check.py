"""`correct` sees the arithmetic: `check.first_steps` through a whole run
on the CPU (`run.drive`, everything after the look for a chip) at tiny
sizes, through both runners at their stated compute type, on one device
and on four.  The faults are `readings.FAULTS`, the ones the chip
readings plant.  The stated format passes; the next format down, APS switched off
where the gradients underflow, a doubled learning rate, a state left as
it was, half of the batch left out and the exchange between chips left
out each come out `correct: false`, by the check that names the fault,
while the first loss still matches the reference.

The limits here are set for the tiny sizes by the rule the traffic
files' limits were set by on the chip (README.md): between the most the
stated format reads and the least its control reads.
"""

from __future__ import annotations

import copy
import dataclasses
import types

import jax
import jax.numpy as jnp
import pytest

import tiny
from benchmark import check, loop, run
from benchmark.readings import FAULTS
from benchmark.reference import sgd

# tiny LM, e5m2-APS, over 5 seeds: all parameters 0.0534-0.0540, the
# worst part alone and the head (embed) no more than 0.056; e5m1
# 0.1037-0.1046; e3m0 0.200-0.208.  sqrt(0.054 x 0.104) = 0.075
LM_TRAFFIC = {**tiny.LM_TRAFFIC, **tiny.limits(
    update_rel_err=0.075, update_rel_err_worst_part=0.077,
    update_rel_err_head=0.075, change_norm_gap=0.15, grad_norm_gap=0.15,
    loss_gap=0.005)}
DP4 = tiny.limits(      # four quantised terms summed in order: 0.0986-0.0990
    update_rel_err=0.14, update_rel_err_worst_part=0.14,
    update_rel_err_head=0.14, change_norm_gap=0.15, grad_norm_gap=0.15,
    loss_gap=0.005)
# small ResNet-50 at the configuration's compute type (bfloat16) and seeded
# weights, 16 images of 32x32 (at 4 images the last stages' batch norms
# see four values and no first step is well conditioned).  e5m2-APS over
# 3 seeds: all parameters 0.153-0.165 (e5m1 0.176-0.187: not told apart,
# so 1.5 a), the worst part 0.232-0.255, the head 0.0547-0.0563 (e5m1
# 0.1045-0.1059), the norm gaps 0.029-0.066 and 0.038-0.048 (half the
# batch 0.66 and 0.68), the losses 0.0015-0.0025 (a doubled learning rate
# 0.065)
VISION_TRAFFIC = {**tiny.VISION_TRAFFIC, "batch_per_chip": 16, **tiny.limits(
    update_rel_err=0.248, update_rel_err_worst_part=0.382,
    update_rel_err_head=0.0767, grad_norm_gap=0.209, change_norm_gap=0.179,
    loss_gap=0.0128)}
SEED = 2 ** 31 + 12345
PASSING = {"losses_finite", "init_loss_in_band", "matches_reference",
           "nothing_compiled_in_window", "replicas_agree",
           "check_reran_the_timed_step"}


def drive(config, traffic, chips=1, break_step=None, program_lr=1.0):
    """A whole run but the look for a chip; `break_step(step, mesh)`
    plants a fault under the timed path, `program_lr` scales the learning
    rate in the program's optimizer only."""
    found = tiny.found(config, traffic, chips)
    build = run.build

    def broken(found_, devices):
        mine = copy.deepcopy(found_)
        opt = mine["config"]["optimizer"]
        key = "lr" if "lr" in opt else "lr_per_256_items"
        opt[key] *= program_lr
        built = build(mine, devices)
        if break_step:
            built["runner"] = dataclasses.replace(
                built["runner"],
                step=break_step(built["runner"].step, built["mesh"]))
        return built

    run.build = broken
    try:
        args = types.SimpleNamespace(seed=SEED, seconds=0.3, trace=0,
                                     keep_trace=None)
        # the CPU's runtime reports no memory statistics: a stand-in
        stats = {"peak_bytes_in_use": 1, "peak_bytes_reserved": 0}
        return run.drive(found, args, jax.devices(), {"bf16_tflops": 1.0},
                         run.CompileCounter().install(),
                         memory_stats=lambda devices: [stats] * len(devices))
    finally:
        run.build = build


def failed(line) -> set:
    return {k for k, ok in line["facts"]["checks"].items() if not ok}


# -------------------------------------------------------------- tests

@pytest.mark.parametrize("wd", [0.0, 1e-4])
def test_plain_sgd_is_the_programs_first_update(wd):
    """`reference/sgd.py`'s first step against `make_optimizer("sgd")`'s,
    float32, bit for bit: the same three operations in the same order."""
    from cpd_tpu.train import make_optimizer

    spec = {"name": "sgd", "momentum": 0.9, "weight_decay": wd, "lr": 0.03}
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    params = {"a": jax.random.normal(keys[0], (33, 7)),
              "b": {"c": jax.random.normal(keys[1], (5,))}}
    grads = {"a": jax.random.normal(keys[2], (33, 7)),
             "b": {"c": jax.random.normal(keys[3], (5,))}}
    tx = make_optimizer("sgd", lambda step: spec["lr"], momentum=0.9,
                        weight_decay=wd)
    want, state = jax.jit(tx.update)(grads, tx.init(params), params)
    lr = sgd.learning_rate(spec, 999)
    got, buf = jax.jit(lambda p, g: sgd.update(p, sgd.init(p), g, spec, lr))(
        params, grads)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (x == y).all()
    for x, y in zip(jax.tree.leaves(buf),
                    jax.tree.leaves(state.momentum_buf)):
        assert (x == y).all()
    # two more steps stay together too (the buffer carries over)
    want2, _ = jax.jit(tx.update)(grads, state, params)
    got2, _ = jax.jit(lambda p, b, g: sgd.update(p, b, g, spec, lr))(
        params, buf, grads)
    for x, y in zip(jax.tree.leaves(got2), jax.tree.leaves(want2)):
        assert (x == y).all()
    assert sgd.learning_rate({"lr_per_256_items": 0.1}, 1024) == 0.4


@pytest.mark.parametrize("chips", [1, 4])
def test_the_stated_format_passes_and_compiles_nothing(chips):
    traffic = {**LM_TRAFFIC, **DP4} if chips == 4 else LM_TRAFFIC
    line = drive(tiny.LM_CONFIG, traffic, chips)
    facts = line["facts"]
    assert line["correct"] and not failed(line), failed(line)
    assert facts["checks"]["update_matches_reference"]
    assert set(facts["step_compiled"].values()) == {0}
    assert facts["first_step_repeats"]
    assert 0.04 < facts["update_rel_err"] < traffic["update_rel_err_max"]
    assert set(facts["update_rel_err_by_part"]) == {"block0", "block1",
                                                    "embed", "ln_f"}
    assert facts["phases_s"]["update_check"] > 0
    # every number compared beside its limit, last in the line
    assert list(line)[-1] == "compared"
    assert {"first_loss_gap", "replica_checksum_spread",
            *tiny.READINGS} == set(line["compared"])
    assert facts["update_rel_err_worst_part"] == max(
        facts["update_rel_err_by_part"].values())
    assert facts["update_rel_err_head"] == facts[
        "update_rel_err_by_part"]["embed"]
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())


UPDATE = {"update_matches_reference", "every_part_update_matches_reference",
          "head_update_matches_reference"}
LM_FAULTS = {
    # the next format down (the control): e5m1 for e5m2
    "next_format_down": (dict(reduce={**tiny.REDUCE, "grad_man": 1}),
                         UPDATE),
    "four_bits": (dict(reduce={**tiny.REDUCE, "grad_exp": 3, "grad_man": 0}),
                  UPDATE),
    "doubled_learning_rate": (dict(program_lr=2.0),
                              UPDATE | {"change_norms_match_reference"}),
    "state_unchanged": (dict(break_step=FAULTS["unchanged"]),
                        UPDATE | {"gradient_norms_match_reference",
                                  "change_norms_match_reference"}),
    "half_of_the_batch": (dict(break_step=FAULTS["half_batch"]),
                          UPDATE | {"gradient_norms_match_reference",
                                    "change_norms_match_reference"}),
}


@pytest.mark.parametrize("fault", sorted(LM_FAULTS))
def test_lm_faults_fail_by_name_and_the_first_loss_still_matches(fault):
    how, must_fail = LM_FAULTS[fault]
    how = dict(how)
    traffic = {**LM_TRAFFIC, "reduce": how.pop("reduce", tiny.REDUCE)}
    line = drive(tiny.LM_CONFIG, traffic, **how)
    assert not line["correct"]
    assert must_fail <= failed(line), (fault, failed(line), line["compared"])
    assert line["facts"]["checks"]["matches_reference"]
    assert not failed(line) & PASSING, failed(line)


def test_aps_off_where_gradients_underflow_fails():
    """e5m2 holds the tiny LM's gradients without APS (a share of 0.003
    lies under 2^-16; at the cells' sizes it is the other way), so the
    pair is taken at e4m3, whose range ends at 2^-9: a third underflows
    (0.090 where APS reads 0.027)."""
    e4m3 = {**tiny.REDUCE, "grad_exp": 4, "grad_man": 3}
    on = drive(tiny.LM_CONFIG, {**LM_TRAFFIC, "reduce": e4m3})
    assert on["correct"] and on["facts"]["update_rel_err"] < 0.04
    off = drive(tiny.LM_CONFIG,
                {**LM_TRAFFIC, "reduce": {**e4m3, "use_aps": False}})
    assert not off["correct"]
    assert off["facts"]["update_rel_err"] > 2 * on["facts"]["update_rel_err"]
    assert "update_matches_reference" in failed(off)
    assert off["facts"]["checks"]["matches_reference"]


def test_exchange_left_out_fails_on_four_devices():
    line = drive(tiny.LM_CONFIG, {**LM_TRAFFIC, **DP4}, chips=4,
                 break_step=FAULTS["without_exchange"])
    assert not line["correct"] and "replicas_agree" in failed(line)
    assert line["compared"]["replica_checksum_spread"]["value"] > 0


NORMS = {"gradient_norms_match_reference", "change_norms_match_reference"}
VISION_FAULTS = {
    "none": ({}, set()),
    # the next format down is seen by the head alone: bfloat16 under batch
    # norms scatters the blocks' gradients by more than e5m1 adds
    "next_format_down": (dict(reduce={**tiny.REDUCE, "grad_man": 1}),
                         {"head_update_matches_reference"}),
    "state_unchanged": (dict(break_step=FAULTS["unchanged"]), UPDATE | NORMS),
    "doubled_learning_rate": (dict(program_lr=2.0),
                              UPDATE | {"change_norms_match_reference",
                                        "step_losses_match_reference"}),
    "half_of_the_batch": (dict(break_step=FAULTS["half_batch"]),
                          UPDATE | NORMS | {"step_losses_match_reference"}),
}


@pytest.mark.parametrize("fault", sorted(VISION_FAULTS))
def test_vision_runner_through_the_check(fault):
    how, must_fail = VISION_FAULTS[fault]
    how = dict(how)
    traffic = {**VISION_TRAFFIC, "reduce": how.pop("reduce", tiny.REDUCE)}
    line = drive(tiny.VISION_CONFIG, traffic, **how)
    assert tiny.VISION_CONFIG["model_kwargs"]["dtype"] == "bfloat16"
    assert set(line["facts"]["step_compiled"].values()) == {0}
    assert must_fail <= failed(line), (fault, failed(line), line["compared"])
    assert not failed(line) & PASSING, failed(line)
    assert line["correct"] == (fault == "none")
    if fault == "none":
        assert line["facts"]["leaves_left_out_of_change"] == 0
        assert len(line["facts"]["update_rel_err_by_part"]) == 19
    if fault == "next_format_down":
        assert failed(line) == must_fail


def test_vision_aps_off_where_gradients_underflow_fails():
    """At e5m2 the small ResNet's gradients lie inside the format's range
    and APS changes no bit of the first update (a scale of a power of two
    commutes with the rounding), so the pair is taken at e4m3, as the
    LM's: without APS the deep blocks' gradients underflow, and the worst
    leaf's norm and the worst part show it (0.48 and 0.44-0.46 where APS
    reads 0.04-0.06 and 0.24-0.25)."""
    e4m3 = {**tiny.REDUCE, "grad_exp": 4, "grad_man": 3}
    on = drive(tiny.VISION_CONFIG, {**VISION_TRAFFIC, "reduce": e4m3})
    assert on["correct"], failed(on)
    off = drive(tiny.VISION_CONFIG,
                {**VISION_TRAFFIC, "reduce": {**e4m3, "use_aps": False}})
    assert not off["correct"]
    assert NORMS | {"every_part_update_matches_reference"} <= failed(off)
    assert off["facts"]["checks"]["matches_reference"]
    assert not failed(off) & PASSING, failed(off)


# four devices, 16 images each, over 2 seeds: all parameters 0.172-0.174,
# the worst part 0.256-0.261, the head 0.092-0.094 (four quantised terms
# summed in order), the norm gaps 0.044-0.124 and 0.051-0.139
VISION_DP4 = tiny.limits(
    update_rel_err=0.261, update_rel_err_worst_part=0.391,
    update_rel_err_head=0.14, grad_norm_gap=0.286, change_norm_gap=0.307,
    loss_gap=0.0128)


@pytest.mark.parametrize("fault", ["none", "without_exchange"])
def test_vision_runner_on_four_devices(fault):
    """The reference's gradient through `shard_map` (each device its own
    slice's batch statistics, as the program's replicas), and the
    exchange left out under the vision runner."""
    line = drive(tiny.VISION_CONFIG, {**VISION_TRAFFIC, **VISION_DP4},
                 chips=4, break_step=FAULTS.get(fault))
    assert set(line["facts"]["step_compiled"].values()) == {0}
    if fault == "none":
        assert line["correct"], (failed(line), line["compared"])
        assert line["compared"]["replica_checksum_spread"]["value"] == 0.0
    else:
        assert not line["correct"] and "replicas_agree" in failed(line)
        assert not failed(line) & (PASSING - {"replicas_agree"})


def test_last_metrics_and_the_step_metric_reader():
    """A step's counters reach the readers: the whole `metrics` of the
    window's last step, fetched after the window."""
    from benchmark.readers import counts

    calls = []

    def step(state, a, b):
        calls.append(a)
        return state + 1, {"loss": jnp.float32(1.0 / (state + 1)),
                           "rows_dropped": jnp.int32(state * 3)}

    w = loop.measure(step, jnp.int32(0), [(0, 0), (1, 1)], group=2,
                     seconds=0.05)
    last = jax.device_get(w.last_metrics)
    assert w.steps == len(calls) == int(w.state)
    assert int(last["rows_dropped"]) == 3 * (w.steps - 1)
    ctx = {"last_metrics": {k: float(v) for k, v in last.items()}}
    assert counts.step_metric(ctx, {"key": "rows_dropped"}) == 3.0 * (
        w.steps - 1)
    assert counts.step_metric(ctx, {"key": "keys_selected"}) is None
    assert counts.step_metric({}, {"key": "loss"}) is None
    assert counts.reading({"readings": {"update_rel_err": 0.25}},
                          {"name": "update_rel_err"}) == 0.25
    assert counts.reading({"readings": {"update_rel_err": float("nan")}},
                          {"name": "update_rel_err"}) is None
    assert counts.reading({}, {"name": "update_rel_err"}) is None


def test_readings_arithmetic():
    """The measures themselves, on sums made by hand."""
    names = ["a/w", "a/b", "c", "d/w"]
    program = {"grad_sq": [4.0, 1.0, 1e-12, 0.0],
               "change_sq": [16.0, 4.0, 0.0, 1.0],
               "losses": [1.0, 2.0, 3.0]}
    reference = {"grad_ref_sq": [4.0, 4.0, 1e-12, 0.0],
                 "update_ref_sq": [9.0, 16.0, 0.0, 4.0],
                 "update_diff_sq": [9.0, 16.0, 0.0, 1.0],
                 "change_ref_sq": [4.0, 4.0, 0.0, 1.0],
                 "losses": [1.0, 2.0, 3.3]}
    readings, facts = check.readings_of(program, reference, names, "d")
    # a and c left as they were, d half a step out: sqrt(26 / 29)
    assert readings["update_rel_err"] == pytest.approx((26 / 29) ** .5)
    assert facts["update_rel_err_by_part"] == {"a": 1.0, "c": float("inf"),
                                               "d": 0.5}
    # every part is held: the worst, and a part with no number is the worst
    assert readings["update_rel_err_worst_part"] == float("inf")
    assert facts["update_rel_err_worst_part_name"] == "c"
    assert readings["update_rel_err_head"] == 0.5
    # |1 - 2| / max(2, median 1); `c` and `d/w` are measured against the
    # median leaf
    assert readings["grad_norm_gap"] == pytest.approx(0.5)
    assert facts["grad_norm_gap_leaf"] == "a/b"
    # |4 - 2| / 2 on `a/w`; `c` and `d/w` have no gradient and are left out
    assert readings["change_norm_gap"] == pytest.approx(1.0)
    assert facts["leaves_left_out_of_change"] == 2
    assert readings["loss_gap"] == pytest.approx(0.3 / 3.3)
    limits = tiny.limits(update_rel_err=0.5, loss_gap=0.1)
    checks, compared = check.judge(readings, limits)
    assert checks == {"update_matches_reference": False,
                      "step_losses_match_reference": True}
    assert compared["update_rel_err"] == {"value": (26 / 29) ** .5,
                                          "limit": 0.5}
    assert set(compared) == {"update_rel_err", "loss_gap"}
    nan = check.judge({"update_rel_err": float("nan")}, limits)[0]
    assert nan == {"update_matches_reference": False}
    with pytest.raises(KeyError, match="update_rel_err_head_max"):
        check.judge(readings, {k: v for k, v in limits.items()
                               if k != "update_rel_err_head_max"})
