"""Measure the pipeline remat-replay tax and GPipe bubble (VERDICT r3 #6).

docs/PERF.md's pipeline section models the cost of `parallel/pipeline.py`
as  t_pp ≈ t_base × (M+P-1)/M × (1 + replay)  — the (P-1)/(M+P-1) bubble
from the tick schedule plus the `remat_stages` forward replay (~1/3 of
stage FLOPs).  Until round 4 both factors were analysis, not measurement.
This script measures them on the 8-device virtual CPU mesh (the only
multi-device surface available without chips; docs/PERF.md carries the
caveat that CPU step-time ratios proxy FLOP ratios, not ICI behavior):

* pp=1 (no bubble, no neighbor traffic) is the baseline — same scan
  machinery, same microbatching, same remat, so ratios isolate the
  schedule effects rather than step-harness differences;
* remat on vs off at fixed (pp, M) isolates the replay tax;
* M sweep at fixed pp isolates the bubble, which must shrink like
  (M+P-1)/M while the remat delta stays put.

Per-device useful FLOPs are held constant across configs: global batch
fixed, dp×pp = 8, so each device sees B/dp tokens through L/pp layers —
the (M+P-1)/M tick overhead and the replay are the only modeled extras.

Writes docs/pp_tax.json and prints a markdown table for docs/PERF.md.
Run solo (no concurrent CPU load) or the medians are noise.
"""

from __future__ import annotations

import json
import os
import re
import statistics
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (
    _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from cpd_tpu.obs.timing import now  # noqa: E402  (the one clock; jax-free)


def measure(dp: int, pp: int, m: int, remat: bool, *, d_model=192,
            n_layers=8, t_seq=128, batch=32, vocab=256, steps=5,
            warmup=2, vocab_pp=False) -> float:
    """Median step seconds for one (dp, pp, M, remat) config."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from cpd_tpu.models import pipelined_lm
    from cpd_tpu.parallel.mesh import make_mesh
    from cpd_tpu.train import make_optimizer, make_pp_train_step
    from cpd_tpu.train.state import TrainState

    mesh = make_mesh(dp=dp, pp=pp)
    kw = dict(vocab_size=vocab, d_model=d_model, n_layers=n_layers,
              n_heads=4, d_ff=4 * d_model)
    model = pipelined_lm(**kw, pp_axis="pp", pp_size=pp,
                         remat_stages=remat, vocab_pp=vocab_pp)
    init_model = pipelined_lm(**kw)
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, vocab, (batch, t_seq)).astype(np.int32))
    tgts = jnp.asarray(np.roll(np.asarray(toks), -1, axis=1))
    variables = init_model.init(jax.random.PRNGKey(0), toks[:1])
    tx = make_optimizer("sgd", lambda s: jnp.float32(0.01), momentum=0.9)
    state = TrainState(step=jnp.zeros([], jnp.int32),
                       params=variables["params"], batch_stats={},
                       opt_state=tx.init(variables["params"]))
    step = make_pp_train_step(model, tx, mesh, n_microbatches=m,
                              donate=False)
    times = []
    for i in range(warmup + steps):
        t0 = now()
        state, metrics = step(state, toks, tgts)
        jax.block_until_ready(metrics["loss"])
        if i >= warmup:
            times.append(now() - t0)
    assert np.isfinite(float(metrics["loss"]))
    return statistics.median(times)


def main() -> int:
    configs = [
        # (dp, pp, M, remat)  — dp*pp == 8 always
        (8, 1, 4, True),    # baseline: scan+remat, no bubble
        (8, 1, 4, False),   # replay tax at pp=1
        (4, 2, 4, True),
        (4, 2, 4, False),
        (2, 4, 4, True),
        (2, 4, 4, False),
        (2, 4, 8, True),    # bubble shrinks with M, replay constant
        (2, 4, 16, True),
    ]
    rows = []
    base = None
    for dp, pp, m, remat in configs:
        sec = measure(dp, pp, m, remat)
        if base is None:
            base = sec
        ticks = (m + pp - 1) / m
        rows.append({"dp": dp, "pp": pp, "M": m, "remat": remat,
                     "step_s": round(sec, 3),
                     "vs_base": round(sec / base, 3),
                     "tick_model": round(ticks, 3)})
        print(f"dp{dp} pp{pp} M{m} remat={int(remat)}: {sec:.3f}s "
              f"({sec / base:.2f}x base; tick model {ticks:.2f}x)",
              flush=True)

    # vocab_pp arms (round 5): the vocab-sharded embed/head against the
    # replicated head at a vocab where the head MATTERS (8192 x 192 =
    # 1.57M table params ~ 3.5x ONE block's params here, and the (B, T,
    # 8192) logits dwarf any single block's activations) — the step-time
    # delta prices the lookup psum + head broadcast + vocab-parallel CE
    # against the replicated head's full logits+CE work per rank.
    # NOTE: regenerating docs/pp_tax.json overwrites it; the round-4
    # capture this tool cannot reproduce (it had pp=8 + repeat arms) is
    # preserved at docs/pp_tax_r4.json
    vp_rows = []
    for dp, pp in [(4, 2), (2, 4)]:
        t_rep = measure(dp, pp, 4, True, vocab=8192)
        t_vp = measure(dp, pp, 4, True, vocab=8192, vocab_pp=True)
        vp_rows.append({"dp": dp, "pp": pp, "vocab": 8192,
                        "replicated_s": round(t_rep, 3),
                        "vocab_pp_s": round(t_vp, 3),
                        "ratio": round(t_vp / t_rep, 3)})
        print(f"dp{dp} pp{pp} vocab8192: replicated {t_rep:.3f}s, "
              f"vocab_pp {t_vp:.3f}s ({t_vp / t_rep:.2f}x)", flush=True)

    out = {"host_cpu": True, "note": "8-device virtual CPU mesh; step-time"
           " ratios proxy FLOP ratios (no real ICI)", "rows": rows,
           "vocab_pp_rows": vp_rows}
    path = os.path.join(_REPO, "docs", "pp_tax.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\nwrote {path}\n")
    print("| dp | pp | M | remat | step s | vs pp1 | tick model |")
    print("|---|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['dp']} | {r['pp']} | {r['M']} | "
              f"{'on' if r['remat'] else 'off'} | {r['step_s']} | "
              f"{r['vs_base']} | {r['tick_model']} |")
    print("\n| dp | pp | vocab | replicated s | vocab_pp s | ratio |")
    print("|---|---|---|---|---|---|")
    for r in vp_rows:
        print(f"| {r['dp']} | {r['pp']} | {r['vocab']} | "
              f"{r['replicated_s']} | {r['vocab_pp_s']} | {r['ratio']} |")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
