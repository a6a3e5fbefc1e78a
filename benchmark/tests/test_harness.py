"""The loop and both runners end to end at a tiny size on the CPU, by
calling them directly (`run.py` itself refuses the CPU)."""

from __future__ import annotations

import copy
import math

import jax
import pytest

import tiny
from benchmark import loop, run


def drive(config, traffic, chips, seconds=0.5):
    found = tiny.found(config, traffic, chips)
    counter = run.CompileCounter().install()
    ready = run.set_up(found, 2 ** 31 + 12345, jax.devices())
    before = counter.snapshot()
    window = loop.measure(ready["runner"].step, ready["state"],
                          ready["batches"], traffic["group"], seconds)
    after = counter.snapshot()
    in_window = {k: after[k] - before[k] for k in after}
    losses = [float(x) for x in jax.device_get(window.losses)]
    sums = (None if chips == 1 else
            [float(c) for c in run.replica_checksums(window.state,
                                                     ready["mesh"])])
    return found, ready, window, losses, in_window, sums


CLEAN = {"traces": 0, "backend_compiles": 0, "cache_requests": 0,
         "cache_hits": 0}


def judged(found, ready, losses, in_window, sums, readings=None,
           step_compiled=CLEAN) -> dict:
    """`run.judge`'s checks; the first steps' readings have tests of
    their own (test_check.py)."""
    return run.judge(found, ready, losses, in_window, sums, readings or {},
                     step_compiled)[0]


@pytest.mark.parametrize("chips", [1, 4])
def test_lm_runner_through_the_loop(chips):
    found, ready, window, losses, in_window, sums = drive(
        tiny.LM_CONFIG, tiny.LM_TRAFFIC, chips)
    checks = judged(found, ready, losses, in_window, sums)
    assert all(checks.values()), checks
    # bf16 compute against the float32 reference, same weights and batch
    assert ready["first_loss"] == pytest.approx(ready["reference_loss"],
                                                rel=1e-3)
    assert ready["runner"].items_per_step == chips * 2 * 128
    assert window.steps == len(losses) >= 3
    # one sample per completed group but the one in flight at the end
    assert len(window.step_samples) == window.steps // 1 - 1
    assert window.seconds >= 0.5
    assert losses[-1] < losses[0]          # it trains
    if chips > 1:
        assert len(sums) == chips


def test_vision_runner_through_the_loop():
    found, ready, window, losses, in_window, sums = drive(
        tiny.VISION_CONFIG, tiny.VISION_TRAFFIC, 1)
    checks = judged(found, ready, losses, in_window, sums)
    assert all(checks.values()), checks
    assert window.steps % tiny.VISION_TRAFFIC["group"] == 0
    assert len(window.step_samples) == window.steps // 2 - 1
    assert ready["runner"].items_per_step == 4


def test_judge_names_each_failure():
    found = tiny.found(tiny.LM_CONFIG,
                       {**tiny.LM_TRAFFIC,
                        **tiny.limits(update_rel_err=0.1)}, 2)
    ready = {"first_loss": math.log(256), "reference_loss": math.log(256)}
    good = {"update_rel_err": 0.06, "grad_norm_gap": 0.5}   # second: null
    checks, compared = run.judge(found, ready, [5.0, 4.0], CLEAN, [1.0, 1.0],
                                 good, CLEAN)
    assert all(checks.values()) and "update_matches_reference" in checks
    assert compared == {
        "first_loss_gap": {"value": 0.0, "limit": 0.05},
        "replica_checksum_spread": {"value": 0.0, "limit": 0.0},
        "update_rel_err": {"value": 0.06, "limit": 0.1}}
    bad = judged(found, {**ready, "reference_loss": 9.0},
                 [5.0, float("nan")], {**CLEAN, "backend_compiles": 1},
                 [1.0, 1.5], {"update_rel_err": 0.11}, {**CLEAN, "traces": 1})
    assert bad == {"losses_finite": False, "init_loss_in_band": True,
                   "matches_reference": False,
                   "nothing_compiled_in_window": False,
                   "replicas_agree": False,
                   "check_reran_the_timed_step": False,
                   "update_matches_reference": False}
    assert not judged(found, {**ready, "first_loss": 30.0}, [1.0], CLEAN,
                      None)["init_loss_in_band"]
    # a replica whose checksum is NaN agrees with nothing
    for sums in ([1.0, float("nan")], [float("nan"), float("nan")]):
        checks, compared = run.judge(found, ready, [5.0], CLEAN, sums, good,
                                     CLEAN)
        assert not checks["replicas_agree"]
        assert compared["replica_checksum_spread"]["value"] == math.inf
    # a traffic file that leaves a reading's limit out is refused
    with pytest.raises(KeyError, match="loss_gap_max"):
        run.judge(tiny.found(tiny.LM_CONFIG, {
            k: v for k, v in found["traffic"].items()
            if k != "loss_gap_max"}, 2), ready, [5.0], CLEAN, None, good,
            CLEAN)


def test_a_backend_without_memory_statistics_is_refused():
    """`memory_peak_bytes` is a measurement: the CPU's runtime reports
    none, and only a test may stand in for it."""
    import jax
    with pytest.raises(RuntimeError, match="memory statistics"):
        run.device_memory_stats(jax.devices()[:1])
    with pytest.raises(KeyError):
        run.peak_bytes({})
    assert run.plain({"a": [float("nan"), 1.5], "b": float("inf")}) == {
        "a": ["nan", 1.5], "b": "inf"}


def test_same_seed_same_inputs_and_large_seeds():
    a = run.seed_key(2 ** 31 + 5)
    assert a.tolist() == run.seed_key(2 ** 31 + 5).tolist()
    assert a.tolist() != run.seed_key(5).tolist()
    assert run.seed_key(2 ** 33 + 1).tolist() == [2, 1]


def test_readers_on_a_window():
    from benchmark.readers import counts, window as wr
    spans = loop.Spans()
    spans.records = [("dispatch", 0.0, 0.25), ("wait", 0.25, 1.0),
                     ("dispatch", 1.0, 1.5)]
    w = loop.Window(state=None, steps=20, seconds=2.0,
                    step_samples=[0.1] * 9 + [0.2], losses=[], spans=spans)
    assert w.last_metrics is None
    ctx = {"window": w, "chips": 2, "items_per_step": 8, "setup_seconds": 3.0,
           "config": copy.deepcopy(tiny.LM_CONFIG),
           "traffic": tiny.LM_TRAFFIC, "peaks": {"bf16_tflops": 1e-6},
           "trace": None, "memory_peak_bytes": 5e6, "param_count": 1000}
    assert wr.rate_per_chip(ctx, {}) == 20 * 8 / 2.0 / 2
    assert wr.step_ms_percentile(ctx, {"q": 90}) == pytest.approx(110.0)
    assert wr.step_ms_median(ctx, {}) == pytest.approx(100.0)
    assert wr.span_ms_per_step(ctx, {"span": "dispatch"}) == 1e3 * 0.75 / 20
    assert counts.peak_hbm_mb(ctx, {}) == 5.0
    # 3 x 1000 elements received, one byte each (e5m2 packed)
    ctx["chips"] = 4
    assert counts.wire_bytes_per_step(ctx, {}) == 3000.0
    from benchmark.flops import dense_lm
    ops = dense_lm.train_flops_per_token(ctx["config"], ctx["traffic"])
    assert counts.mfu_pct(ctx, {}) == pytest.approx(
        100 * ops * (20 * 8 / 2.0 / 4) / 1e6)
    # a reader with nothing to read returns nothing
    from benchmark.readers import trace as tr
    assert tr.idle_pct(ctx, {}) is None
    assert tr.busy_ms_per_step(ctx, {}) is None


def test_flops_match_the_published_counts():
    from benchmark.flops import dense_lm, resnet
    real = run.discover()
    lm = next(v for v in real.values()
              if v["config"]["runner"] == "train_lm")
    assert dense_lm.matmul_params(lm["config"]) == 534_773_760
    assert dense_lm.train_flops_per_token(
        lm["config"], lm["traffic"]) == pytest.approx(3.51e9, rel=2e-3)
    assert resnet.forward_macs(224, 1000) == pytest.approx(4.09e9, rel=2e-3)
    # root bench.py's FLOPS_PER_IMG
    assert resnet.train_flops_per_image(
        {"image_size": 224, "classes": 1000}, {}) == pytest.approx(
            24.6e9, rel=5e-3)


def test_vision_reference_is_the_models_own_arithmetic_in_float32():
    """With float32 compute the step's first loss and the plain
    reference's agree closely, so the distance seen at bfloat16 is the
    compute type's and not a difference of architecture."""
    config = copy.deepcopy(tiny.VISION_CONFIG)
    config["model_kwargs"]["dtype"] = "float32"
    ready = run.set_up(tiny.found(config, tiny.VISION_TRAFFIC, 1), 7,
                       jax.devices())
    assert ready["first_loss"] == pytest.approx(ready["reference_loss"],
                                                rel=2e-3)
