"""Headline benchmark: ResNet-50/ImageNet-shape training throughput per chip.

The reference's only quantitative scale claim is ResNet-50/ImageNet, 90
epochs in ">30 hours" on 8x V100 — an implied upper bound of ~133 img/s/chip
(BASELINE.md; reference README.md:118).  This bench measures the same
workload shape on TPU chips: full training step (fwd+bwd+optimizer) of
ResNet-50 at 224x224, batch 32/chip (main.py:32-33), bf16 compute / fp32
master params, with the e5m2 APS gradient pipeline engaged exactly as the
reference's flagship config runs it (--use_APS --grad_exp 5 --grad_man 2).

One process, which holds every visible chip.  It refuses to run unless
`jax.devices()[0].platform == "tpu"` (`ops.require_tpu`: exit 2, nothing
computed): a timing from any other backend is not a device number and is
never written under these keys.  Any phase that raises ends the run with a traceback and a
nonzero exit — nothing is caught and turned into a note.  On success the
last line of stdout is ONE JSON object: {"metric", "value", "unit",
"vs_baseline", "device": {platform, kind, count}, ...}.

Phases, in order: the flagship faithful-mode measurement and its fast-mode
twin; the analytic bytes-on-wire ledger for every reduction transport
(parallel/ring.py's formulas — counts, not timings); a batch-128 scaling
point; and the transformer-LM training arms (e5m2 APS, one-shot and
chunked attention).  Reported alongside the headline img/s:
`tflops_per_sec` and `mfu_pct` from FLOPS_PER_IMG and the bf16 peak that
PEAK_BF16_TFLOPS lists for the device kind; a kind the table lacks is an
error, not a default.

Env knobs: BENCH_ITERS (steps per measurement, default 20),
BENCH_FUSE_STEPS (steps scan-fused per dispatch, default 16),
BENCH_PROFILE_DIR (also write a jax.profiler trace of a few steps).
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

from cpd_tpu.obs.timing import now  # the one clock; jax-free

BASELINE_IMG_PER_SEC_PER_CHIP = 133.0  # derived in BASELINE.md / SURVEY.md §6
# ResNet-50 fwd+bwd at 224x224: forward is 4.1 GMACs = 8.2 GFLOP/img (a
# MAC is TWO flops — the same convention as the chip's peak), x3 for
# fwd+bwd = 24.6 GFLOP/img.  Cross-checked against the traced train-step
# graph, which holds 28.2 GFLOP/img of GEMM work (tools/mfu_model.py; the
# extra is strided-dgrad overhead XLA really executes) — 24.6 is the
# conservative standard-MFU convention (docs/PERF.md).
FLOPS_PER_IMG = 24.6e9
# Peak dense bf16 TFLOP/s per chip, keyed by `jax.Device.device_kind`.
# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (197 TFLOP/s bf16 per chip); jax reports that chip as "TPU v5 lite".
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0}


def peak_tflops(device_kind: str) -> float:
    """The table's bf16 peak for `device_kind`; unknown kinds are an
    error — a utilization against a guessed peak is not a measurement."""
    if device_kind not in PEAK_BF16_TFLOPS:
        raise KeyError(
            f"no bf16 peak recorded for device kind {device_kind!r}; add "
            f"it to bench.PEAK_BF16_TFLOPS with its source (have "
            f"{sorted(PEAK_BF16_TFLOPS)})")
    return PEAK_BF16_TFLOPS[device_kind]


def _measure(jax, step, state, x, y, iters: int, windows: int = 4,
             imgs_per_call: int | None = None):
    """Compile (first call) then time `iters` calls in `windows` separate
    windows, each ended by `block_until_ready` on the step's outputs;
    returns (best-window img/s, median img/s, state)."""
    if imgs_per_call is None:
        imgs_per_call = x.shape[0]
    state, metrics = step(state, x, y)
    jax.block_until_ready((state, metrics))

    per = max(1, iters // windows)
    rates = []
    for _ in range(windows):
        t0 = now()
        for _ in range(per):
            state, metrics = step(state, x, y)
        jax.block_until_ready((state, metrics))
        dt = now() - t0
        rates.append(imgs_per_call * per / dt)
    rates.sort()
    return rates[-1], rates[len(rates) // 2], state


def run_bench(devices, profile_dir: str | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from cpd_tpu.models import resnet50, transformer_lm
    from cpd_tpu.parallel.dist import replicate
    from cpd_tpu.parallel.mesh import make_mesh
    from cpd_tpu.parallel.ring import ring_transport_bytes, transport_table
    from cpd_tpu.train import (create_train_state, make_lm_train_step,
                               make_optimizer, warmup_step_decay)
    from cpd_tpu.train.state import TrainState
    from cpd_tpu.train.step import make_multi_train_step

    peak = peak_tflops(devices[0].device_kind)
    batch, size = 32, 224
    n_dev = len(devices)
    mesh = make_mesh(dp=n_dev)
    model = resnet50(dtype=jnp.bfloat16)
    schedule = warmup_step_decay(3.2, 500, [3000, 6000])  # main.py:237-252 shape
    tx = make_optimizer("sgd", schedule, momentum=0.9, weight_decay=1e-4)

    # BENCH_FUSE_STEPS steps scan-fused into one executable (the idiomatic
    # TPU training-loop shape: per-dispatch host overhead is paid once per
    # `fuse` steps).  Semantically identical to calling the single step k
    # times — verified bitwise in tests/test_train.py.  16 x 32 bf16
    # inputs ≈ 150 MB, comfortably inside a v5e chip's HBM.
    fuse = int(os.environ.get("BENCH_FUSE_STEPS", "16"))
    iters = int(os.environ.get("BENCH_ITERS", "20"))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(fuse, batch * n_dev, size, size,
                              3).astype(np.float32), jnp.bfloat16)
    y = jnp.asarray(rng.randint(0, 1000,
                                (fuse, batch * n_dev)).astype(np.int32))

    def fresh_state(sample):
        # fresh per measurement: the step donates its state argument
        return replicate(create_train_state(model, tx, sample,
                                            jax.random.PRNGKey(0)), mesh)

    out = {"metric": "resnet50_train_img_per_sec_per_chip",
           "unit": "img/s/chip",
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind, "count": n_dev},
           "mode": "faithful"}
    for mode in ("faithful", "fast"):
        state = fresh_state(x[0, :2])
        n_params = sum(l.size for l in jax.tree.leaves(state.params))
        step = make_multi_train_step(
            model, tx, mesh, fuse, use_aps=True, grad_exp=5, grad_man=2,
            mode=mode, donate=True)
        best, median, _ = _measure(jax, step, state, x, y,
                                   max(1, iters // fuse),
                                   imgs_per_call=fuse * batch * n_dev)
        if mode == "faithful":
            faithful_step = step
            per_chip = best / n_dev
            tflops = per_chip * FLOPS_PER_IMG / 1e12
            out.update(
                value=round(per_chip, 2),
                vs_baseline=round(per_chip / BASELINE_IMG_PER_SEC_PER_CHIP,
                                  3),
                median_img_per_sec_per_chip=round(median / n_dev, 2),
                tflops_per_sec=round(tflops, 1),
                mfu_pct=round(100.0 * tflops / peak, 1))
        else:
            out["fast_mode_img_per_sec_per_chip"] = round(best / n_dev, 2)

    # The gradient-reduction transport ledger: analytic per-device bytes
    # on the wire for this model's gradients under every transport, at
    # the measured world size and at the W=8 reference.  Counts from
    # shapes (parallel/ring.py owns the formulas), not timings.
    out["reduction"] = {
        "transport_mode": "faithful",  # the headline measurement's
        "grad_elements": n_params,
        "format": [5, 2],
        "bytes_on_wire_per_device": transport_table(n_params, n_dev, 5, 2),
        "w8_reference": transport_table(n_params, 8, 5, 2),
        "block_scaled_ring_bytes_per_device": ring_transport_bytes(
            n_params, n_dev, 5, 2, block_size=128),
    }

    # A larger-batch scaling point.  bs 32 is the reference-parity
    # headline (main.py:32) but underfills the MXU; bs 128 shows what the
    # chip does when fed.  fuse drops to 4 so the fused input block stays
    # ~300 MB.
    big_bs, big_fuse = 128, 4
    xb = jnp.asarray(rng.randn(big_fuse, big_bs * n_dev, size, size,
                               3).astype(np.float32), jnp.bfloat16)
    yb = jnp.asarray(rng.randint(
        0, 1000, (big_fuse, big_bs * n_dev)).astype(np.int32))
    big_step = make_multi_train_step(model, tx, mesh, big_fuse,
                                     use_aps=True, grad_exp=5, grad_man=2,
                                     mode="faithful", donate=True)
    big_ips, _, _ = _measure(jax, big_step, fresh_state(xb[0, :2]), xb, yb,
                             max(1, iters // big_fuse), windows=3,
                             imgs_per_call=big_fuse * big_bs * n_dev)
    out["bs128_img_per_sec_per_chip"] = round(big_ips / n_dev, 2)
    out["bs128_mfu_pct"] = round(
        100.0 * (big_ips / n_dev) * FLOPS_PER_IMG / 1e12 / peak, 1)

    # Transformer-LM throughput (tokens/s/chip) with the same e5m2 APS
    # pipeline.  The reference has no LM baseline, so this is reported
    # alongside, never as the headline metric.
    seq, lm_bs = 1024, 8
    lm_kw = dict(vocab_size=32000, d_model=512, n_layers=8, n_heads=8,
                 d_ff=2048)
    arr = rng.randint(0, 32000, (lm_bs * n_dev, seq)).astype(np.int32)
    toks = jnp.asarray(arr)
    tgts = jnp.asarray(np.roll(arr, -1, axis=1))
    lm_tx = make_optimizer("sgd", schedule, momentum=0.9)
    # one-shot softmax vs the online-softmax K/V-block scan (the
    # O(T·block) score-memory path)
    for key, attn_kw in (("lm_train_tok_per_sec_per_chip", {}),
                         ("lm_chunked_tok_per_sec_per_chip",
                          {"attn_impl": "chunked"})):
        lm = transformer_lm(**lm_kw, dtype=jnp.bfloat16, **attn_kw)
        params = lm.init(jax.random.PRNGKey(2), toks[:1])["params"]
        lm_state = replicate(TrainState(
            step=jnp.asarray(0, jnp.int32), params=params, batch_stats={},
            opt_state=lm_tx.init(params)), mesh)
        lm_step = make_lm_train_step(lm, lm_tx, mesh, use_aps=True,
                                     grad_exp=5, grad_man=2, donate=False)
        tok_rate, _, _ = _measure(jax, lm_step, lm_state, toks, tgts, 12,
                                  windows=3,
                                  imgs_per_call=lm_bs * n_dev * seq)
        out[key] = round(tok_rate / n_dev, 1)

    if profile_dir:
        with jax.profiler.trace(profile_dir):
            _measure(jax, faithful_step, fresh_state(x[0, :2]), x, y, 2,
                     windows=1, imgs_per_call=fuse * batch * n_dev)
    return out


def main() -> int:
    from cpd_tpu.ops import require_tpu
    from cpd_tpu.utils import enable_compile_cache

    devices = require_tpu("bench")
    enable_compile_cache()
    out = run_bench(devices,
                    profile_dir=os.environ.get("BENCH_PROFILE_DIR"))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
