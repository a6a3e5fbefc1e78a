"""The golden APS accuracy experiment — the reference's artifact claim,
reproduced end-to-end on the virtual 8-device mesh.

The reference repo's entire evaluation is "train with and without APS and
compare accuracy curves" (reference README.md:70-79,153-154: "using APS, we
can improve the testing accuracies of training with low-precision
gradients").  This script runs that experiment on the cpd_tpu stack: a
fixed-seed CIFAR-10-shaped workload (real CIFAR-10 if on disk, else the
learnable synthetic set, data/cifar.py), trained at full fp32 gradients and
at low-precision gradient formats with APS off and on, through the faithful
rank-ordered quantized all-reduce over dp=8 x emulate_node=2 (a 16-rank
emulated cluster, README.md:76-79's quick-start shape).

Outputs (default docs/golden/):
    results.json   — final Prec@1 per config + the asserted orderings
    curves.png     — train-loss curves + final-accuracy bars

Expected ordering (checked, exit 1 on violation):
    aps >= noaps + margin   and   aps ≈ fp32     for each low-prec format
A short CI version runs in tests/test_golden.py.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


CONFIGS = [
    # tag, grad_exp, grad_man, use_aps
    ("fp32", 8, 23, False),
    ("e4m3_noaps", 4, 3, False),
    ("e4m3_aps", 4, 3, True),
    ("e3m4_noaps", 3, 4, False),
    ("e3m4_aps", 3, 4, True),
    # SR gradient pipeline (beyond-reference): unbiased rounding instead
    # of exponent shifting — far above the RTNE collapse, below APS.
    # Committed run: 92.84 (vs noaps 31.72, aps 94.93).  The margin is
    # conservative (+15) because SR trades bias for noise; note the
    # PRE-rank-decorrelation code measured 74.6-90.1 across seeds, so a
    # result back in that range suggests the coherent-rounding regression
    # (parallel/dist.py k_pre), not ordinary seed variance.
    ("e3m4_sr_noaps", 3, 4, False, ("--grad-rounding", "stochastic")),
]

# Second arm (capability beyond the reference): momentum buffer held in
# eXmY (train/optim.py quant_sgd).  Same claim shape as APS: naive
# low-precision state loses accuracy, the quantized Kahan residual
# recovers it.  Gradients stay fp32 so the effect isolates the optimizer.
OPT_CONFIGS = [
    # tag, extra CLI flags
    ("opt_fp32", []),
    ("opt_e4m3_naive", ["--opt_exp", "4", "--opt_man", "3"]),
    ("opt_e4m3_kahan", ["--opt_exp", "4", "--opt_man", "3",
                        "--opt_kahan"]),
    # stochastic rounding: the OTHER cure for low-precision update
    # stagnation — unbiased random round direction instead of a
    # deterministic residual.  Exploration (seeds 0 and 7: 95.20 / 94.80
    # vs naive 92.97) sits between naive and Kahan, as theory predicts.
    ("opt_e4m3_sr", ["--opt_exp", "4", "--opt_man", "3",
                     "--opt-rounding", "stochastic"]),
]


def _run_tagged(tagged_flags, iters: int, save_root: str, batch_size: int,
                emulate_node: int, peak_lr: float, data_root, arch: str,
                mode: str, quiet: bool) -> dict:
    """Shared runner: train each (tag, extra_flags) config through the
    ResNet-18 CLI; returns {tag: {"prec1": float, "loss": [(step, v)]}}."""
    from resnet18_cifar.train import main

    out = {}
    for tag, extra in tagged_flags:
        save = os.path.join(save_root, tag)
        # from-scratch experiment: a stale checkpoint from a previous run
        # would auto-resume at max_iter and train nothing
        shutil.rmtree(save, ignore_errors=True)
        argv = ["--arch", arch, "--batch_size", str(batch_size),
                "--max-iter", str(iters), "--val_freq", str(iters),
                "--print_freq", "100000" if quiet else "50",
                "--peak-lr", str(peak_lr), "--save_path", save,
                "--emulate_node", str(emulate_node), "--mode", mode] + extra
        if data_root:
            argv += ["--data-root", data_root]
        res = main(argv)
        losses = []
        jsonl = os.path.join(save, "logs", "scalars.jsonl")
        if os.path.isfile(jsonl):
            with open(jsonl) as f:
                for line in f:
                    rec = json.loads(line)
                    if rec.get("tag") == "train/loss":
                        losses.append((rec["step"], rec["value"]))
        out[tag] = {"prec1": res["best_prec1"], "loss": losses,
                    "diverged": bool(res.get("diverged"))}
        note = "  [DIVERGED]" if res.get("diverged") else ""
        print(f"== {tag}: Prec@1 {res['best_prec1']:.2f}{note}", flush=True)
    return out


def run_experiment(iters: int, save_root: str, batch_size: int = 16,
                   emulate_node: int = 2, peak_lr: float = 0.4,
                   configs=CONFIGS, data_root=None, arch: str = "tiny",
                   mode: str = "fast", quiet: bool = True) -> dict:
    """Train every gradient-precision config.

    `mode="fast"` uses quantize->psum->requantize; the ordered faithful
    path is bit-covered by tests/test_parallel.py — for the accuracy-
    ordering claim both modes carry the same precision at the wire, and
    fast keeps the experiment CPU-affordable."""
    tagged = [(tag, ["--grad_exp", str(ge), "--grad_man", str(gm)]
               + (["--use_APS"] if aps else [])
               + [f for flags in extra for f in flags])
              for tag, ge, gm, aps, *extra in configs]
    return _run_tagged(tagged, iters, save_root, batch_size, emulate_node,
                       peak_lr, data_root, arch, mode, quiet)


def run_opt_experiment(iters: int, save_root: str, batch_size: int = 16,
                       emulate_node: int = 2, peak_lr: float = 0.4,
                       configs=OPT_CONFIGS, data_root=None,
                       arch: str = "tiny", mode: str = "fast",
                       quiet: bool = True) -> dict:
    """Train every optimizer-precision config; {tag: {"prec1": ...}}."""
    return _run_tagged(list(configs), iters, save_root, batch_size,
                       emulate_node, peak_lr, data_root, arch, mode, quiet)


# Third arm (capability beyond the reference): the transformer LM under
# the same APS claim — at an aggressive gradient format the un-scaled
# quantized all-reduce stalls training, APS recovers it.  Loss (lower
# better) replaces Prec@1 as the metric.
LM_CONFIGS = [
    ("lm_fp32", 8, 23, False),
    ("lm_e3m4_noaps", 3, 4, False),
    ("lm_e3m4_aps", 3, 4, True),
    # SR gradient pipeline on the LM: unbiased rounding alone recovers
    # most of the no-APS stall (exploration seeds 0/7: 2.699 / 2.722 vs
    # noaps 4.056, aps 2.604)
    ("lm_e3m4_sr_noaps", 3, 4, False, ("--grad-rounding", "stochastic")),
]


def run_lm_experiment(iters: int, save_root: str, configs=LM_CONFIGS,
                      quiet: bool = True) -> dict:
    """Train each gradient-precision config through the LM CLI on the
    8-device mesh; returns {tag: {"loss": float, "accuracy": float}}."""
    from lm.train import main

    out = {}
    for tag, ge, gm, aps, *extra in configs:
        save = os.path.join(save_root, tag)
        shutil.rmtree(save, ignore_errors=True)   # see _run_tagged
        argv = ["--seq-len", "32", "--d-model", "32", "--n-layers", "2",
                "--n-heads", "4", "--vocab-size", "64", "--batch-size",
                "2", "--max-iter", str(iters), "--base-lr", "0.05",
                "--print-freq", "100000" if quiet else "50",
                "--val-freq", str(iters), "--mode", "fast",
                "--grad_exp", str(ge), "--grad_man", str(gm),
                "--save-path", save]
        if aps:
            argv.append("--use_APS")
        for flags in extra:
            argv.extend(flags)
        res = main(argv)
        out[tag] = {"loss": res["loss"], "accuracy": res["accuracy"],
                    "diverged": bool(res.get("diverged"))}
        print(f"== {tag}: loss {res['loss']:.4f} "
              f"acc {100 * res['accuracy']:.1f}", flush=True)
    return out


def check_lm_ordering(results: dict, margin: float = 0.5,
                      recover: float = 0.3) -> list[str]:
    """APS recovers the LM loss the naive low-precision reduce loses.

    A diverged (or NaN) no-APS arm counts as infinitely bad — divergence
    at the aggressive format is the strongest form of the claim's
    premise, not a harness failure.  A diverged APS or fp32 arm IS a
    failure."""
    def loss_of(tag, bad_is_inf):
        rec = results[tag]
        v = rec["loss"]
        if rec.get("diverged") or not math.isfinite(v):
            return float("inf") if bad_is_inf else float("nan")
        return v

    fp32 = loss_of("lm_fp32", bad_is_inf=False)
    noaps = loss_of("lm_e3m4_noaps", bad_is_inf=True)
    aps = loss_of("lm_e3m4_aps", bad_is_inf=False)
    ok_gain = aps <= noaps - margin
    ok_recover = aps <= fp32 + recover
    checks = [
        f"lm e3m4: aps loss {aps:.3f} <= noaps {noaps:.3f} - {margin} -> "
        f"{'OK' if ok_gain else 'VIOLATED'}",
        f"lm e3m4: aps loss {aps:.3f} <= fp32 {fp32:.3f} + {recover} -> "
        f"{'OK' if ok_recover else 'VIOLATED'}",
    ]
    if "lm_e3m4_sr_noaps" in results:
        # the SR rescue on the LM (exploration: 2.70/2.72 across seeds vs
        # the 4.06 stall); 0.5 recover margin absorbs SR's seed noise
        sr = loss_of("lm_e3m4_sr_noaps", bad_is_inf=False)
        ok_sr = (sr <= noaps - margin) and (sr <= fp32 + 0.5)
        checks.append(
            f"lm e3m4: sr_noaps loss {sr:.3f} <= noaps {noaps:.3f} - "
            f"{margin} and <= fp32 {fp32:.3f} + 0.5 -> "
            f"{'OK' if ok_sr else 'VIOLATED'}")
    return checks


def check_opt_ordering(results: dict, margin: float = 1.0,
                       recover: float = 2.0) -> list[str]:
    """Kahan-compensated eXmY momentum recovers what naive loses; so does
    unbiased stochastic rounding (by a smaller, noisier margin)."""
    fp32 = results["opt_fp32"]["prec1"]
    naive = results["opt_e4m3_naive"]["prec1"]
    kahan = results["opt_e4m3_kahan"]["prec1"]
    ok_gain = kahan >= naive + margin
    ok_recover = kahan >= fp32 - recover
    checks = [
        f"opt e4m3: kahan {kahan:.2f} >= naive {naive:.2f} + {margin} -> "
        f"{'OK' if ok_gain else 'VIOLATED'}",
        f"opt e4m3: kahan {kahan:.2f} >= fp32 {fp32:.2f} - {recover} -> "
        f"{'OK' if ok_recover else 'VIOLATED'}",
    ]
    if "opt_e4m3_sr" in results:
        sr = results["opt_e4m3_sr"]["prec1"]
        ok_sr = sr >= naive + margin
        checks.append(
            f"opt e4m3: sr {sr:.2f} >= naive {naive:.2f} + {margin} -> "
            f"{'OK' if ok_sr else 'VIOLATED'}")
    return checks


def check_ordering(results: dict, margin: float = 2.0) -> list[str]:
    """The artifact claim: APS recovers the accuracy low-precision loses."""
    checks = []
    fp32 = results["fp32"]["prec1"]
    for fmt in ("e4m3", "e3m4"):
        noaps = results.get(f"{fmt}_noaps")
        aps = results.get(f"{fmt}_aps")
        if noaps is None or aps is None:
            continue
        ok_gain = aps["prec1"] >= noaps["prec1"] + margin
        ok_recover = aps["prec1"] >= fp32 - 5.0
        checks.append(f"{fmt}: aps {aps['prec1']:.2f} >= noaps "
                      f"{noaps['prec1']:.2f} + {margin} -> "
                      f"{'OK' if ok_gain else 'VIOLATED'}")
        checks.append(f"{fmt}: aps {aps['prec1']:.2f} >= fp32 {fp32:.2f} - 5 "
                      f"-> {'OK' if ok_recover else 'VIOLATED'}")
    if "e3m4_sr_noaps" in results and "e3m4_noaps" in results:
        # SR rescue: unbiased rounding alone recovers most of what the
        # un-APS'd RTNE reduction loses.  Conservative +15 margin: SR is
        # noisy by construction (observed 74.6-90.1 across seeds vs the
        # 31.7 collapse); APS's deterministic shifting remains the best
        # arm and is asserted above.
        sr = results["e3m4_sr_noaps"]["prec1"]
        noaps = results["e3m4_noaps"]["prec1"]
        ok_sr = sr >= noaps + 15.0
        checks.append(f"e3m4: sr_noaps {sr:.2f} >= noaps {noaps:.2f} + 15 "
                      f"-> {'OK' if ok_sr else 'VIOLATED'}")
    return checks


def plot(results: dict, path: str) -> None:
    import matplotlib
    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    fig, (ax1, ax2) = plt.subplots(1, 2, figsize=(11, 4))
    for tag, rec in results.items():
        if rec["loss"]:
            steps, vals = zip(*rec["loss"])
            ax1.plot(steps, vals, label=tag)
    ax1.set_xlabel("iteration")
    ax1.set_ylabel("train loss")
    ax1.set_title("training loss")
    ax1.legend()
    tags = list(results)
    ax2.bar(range(len(tags)), [results[t]["prec1"] for t in tags])
    ax2.set_xticks(range(len(tags)), tags, rotation=30, ha="right")
    ax2.set_ylabel("final Prec@1 (%)")
    ax2.set_title("APS recovers low-precision accuracy")
    fig.tight_layout()
    fig.savefig(path, dpi=110)
    print(f"wrote {path}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--iters", type=int, default=400)
    p.add_argument("--out", default=os.path.join(_REPO, "docs", "golden"))
    p.add_argument("--save-root", default="/tmp/cpd_tpu_golden")
    p.add_argument("--data-root", default=None)
    p.add_argument("--margin", type=float, default=2.0,
                   help="APS-arm min accuracy gain (aps vs noaps)")
    p.add_argument("--opt-margin", type=float, default=1.0,
                   help="optimizer-arm min gain (kahan vs naive)")
    p.add_argument("--lm-iters", type=int, default=150,
                   help="LM-arm iterations (separation shows by ~150)")
    p.add_argument("--lm-margin", type=float, default=0.5,
                   help="LM-arm min loss gain (aps vs noaps)")
    p.add_argument("--lm-recover", type=float, default=0.3,
                   help="LM-arm max loss gap to fp32")
    args = p.parse_args(argv)

    results = run_experiment(args.iters, args.save_root,
                             data_root=args.data_root)
    checks = check_ordering(results, args.margin)
    opt_results = run_opt_experiment(args.iters,
                                     os.path.join(args.save_root, "opt"),
                                     data_root=args.data_root)
    opt_checks = check_opt_ordering(opt_results,
                                    margin=args.opt_margin)
    checks += opt_checks
    lm_results = run_lm_experiment(args.lm_iters,
                                   os.path.join(args.save_root, "lm"))
    checks += check_lm_ordering(lm_results, margin=args.lm_margin,
                                recover=args.lm_recover)
    os.makedirs(args.out, exist_ok=True)
    payload = {
        "iters": args.iters,
        "lm_iters": args.lm_iters,
        "workload": "CIFAR-10-shaped, tiny CNN, dp=8 x emulate_node=2 "
                    "(16-rank emulated cluster), faithful-precision wire; "
                    "LM arm: 2L transformer, dp=8, Markov token stream",
        "prec1": {t: r["prec1"] for t, r in results.items()},
        "opt_prec1": {t: r["prec1"] for t, r in opt_results.items()},
        "lm_loss": {t: r["loss"] for t, r in lm_results.items()},
        "checks": checks,
    }
    with open(os.path.join(args.out, "results.json"), "w") as f:
        json.dump(payload, f, indent=2)
    plot(results, os.path.join(args.out, "curves.png"))
    for c in checks:
        print(c)
    return 1 if any("VIOLATED" in c for c in checks) else 0


if __name__ == "__main__":
    # The documented workload is the 8-device VIRTUAL CPU mesh (the JAX
    # emulate-node analog, SURVEY.md §4c) — force it before jax imports:
    # the experiment needs eight devices, and must not take a chip.
    import re

    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                    os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    raise SystemExit(main())
