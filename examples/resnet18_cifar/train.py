"""ResNet18/CIFAR-10 trainer — parity with the reference flagship entry
`example/ResNet18/tools/mix.py` (flags mix.py:29-44, YAML merge :69-72,
schedule :181-198, loop :224-356), rebuilt on the shared cpd_tpu harness.

Where the reference runs one Python loop per parameter per micro-batch
(SURVEY.md §3.1), here the whole quantized step — emulate-node scan, APS,
low-precision ordered all-reduce, LARS/SGD — is ONE jitted shard_map
program per step (cpd_tpu/train/step.py).

Usage (mirrors README.md:76-79's single-host quick start):
    python examples/resnet18_cifar/train.py --use_APS --grad_exp 5 \
        --grad_man 2 --emulate_node 8
"""

from __future__ import annotations

import argparse
import math
import os
import sys

import numpy as np

# Make the repo importable when run as a script (the reference required a
# manual PYTHONPATH export, README.md:39; here the entry bootstraps itself).
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from cpd_tpu.obs.timing import now  # noqa: E402  (the one clock; jax-free)


def build_parser() -> argparse.ArgumentParser:
    here = os.path.dirname(os.path.abspath(__file__))
    p = argparse.ArgumentParser(description="cpd_tpu ResNet18/CIFAR10")
    # the reference's surface (mix.py:29-44)
    p.add_argument("--config", default=os.path.join(here, "configs",
                                                    "res18_cifar.yaml"))
    p.add_argument("--dist", action="store_true",
                   help="multi-host: call jax.distributed.initialize()")
    p.add_argument("--load-path", default="", type=str)
    p.add_argument("--init-from-torch", default="", type=str,
                   help="warm-start params+BN stats from a reference "
                        "CPDtorch .pth checkpoint (res_cifar arch; "
                        "cpd_tpu.interop converts the layout)")
    p.add_argument("--export-torch", default="", type=str,
                   help="after the run (train or -e), write params+BN "
                        "stats as a reference-format .pth (state_dict "
                        "wrapper, res_cifar key layout) loadable by the "
                        "torch reference — the reverse migration path")
    p.add_argument("--grad_exp", default=5, type=int)
    p.add_argument("--grad_man", default=2, type=int)
    p.add_argument("--grad-rounding", default="nearest",
                   choices=["nearest", "stochastic"],
                   help="rounding of every cast in the gradient pipeline "
                        "(emulate-node + all-reduce): stochastic = "
                        "unbiased SR, the alternative to APS's exponent "
                        "shifting for sub-ulp gradient survival")
    p.add_argument("--grad-seed", default=0, type=int,
                   help="PRNG seed for --grad-rounding stochastic")
    p.add_argument("--resume-opt", action="store_true")
    p.add_argument("--use_lars", action="store_true")
    p.add_argument("--use_APS", action="store_true")
    p.add_argument("--use_kahan", action="store_true")
    # optimizer-state precision (beyond the reference): hold the SGD
    # momentum buffer in eXmY, the state analog of --grad_exp/--grad_man
    p.add_argument("--opt_exp", default=8, type=int)
    p.add_argument("--opt_man", default=23, type=int)
    p.add_argument("--opt_kahan", action="store_true",
                   help="Kahan-compensate the quantized momentum buffer")
    p.add_argument("--opt-rounding", default="nearest",
                   choices=["nearest", "stochastic"],
                   help="rounding of the eXmY momentum-buffer casts: "
                        "stochastic = unbiased SR (cures sub-ulp/2 update "
                        "stagnation; train/optim.py quant_sgd)")
    p.add_argument("--opt-seed", default=0, type=int,
                   help="PRNG seed for --opt-rounding stochastic")
    p.add_argument("--optimizer", default="auto",
                   choices=["auto", "sgd", "nesterov", "lars",
                            "quant_sgd", "shampoo-lite"],
                   help="optimizer family.  'auto' (default) keeps the "
                        "legacy flag-driven choice (--use_lars / "
                        "--opt_exp&co -> quant_sgd, else sgd).  "
                        "'shampoo-lite' is the second-order optimizer "
                        "riding the quantized ring (ISSUE 15, "
                        "train/optim.py ShampooLite): per-leaf Gram "
                        "statistics through the eXmY Kahan qgemm, "
                        "cross-replica statistics reduced over the "
                        "quantized ring, L^-1/4 G R^-1/4 "
                        "preconditioning grafted to the SGD norm")
    p.add_argument("--shampoo-stat-exp", default=8, type=int,
                   help="eXmY exponent bits of the Shampoo-lite Gram "
                        "statistics (8,23 = fp32 statistics)")
    p.add_argument("--shampoo-stat-man", default=23, type=int,
                   help="eXmY mantissa bits of the Shampoo-lite Gram "
                        "statistics")
    p.add_argument("--shampoo-stat-mode", default="ring",
                   choices=["ring", "gather"],
                   help="transport of the cross-replica statistics "
                        "reduction: quantized ring (default) or "
                        "all_gather + ordered scan")
    p.add_argument("-e", "--evaluate", action="store_true")
    p.add_argument("--emulate_node", default=1, type=int)
    # YAML-backed keys (mix.py:69-72 merges the YAML onto args); a CLI
    # value beats the YAML one, so default=None means "take the YAML's".
    p.add_argument("--arch", default=None, type=str)
    p.add_argument("--batch_size", default=None, type=int)
    p.add_argument("--max_epoch", default=None, type=int)
    p.add_argument("--save_path", default=None, type=str)
    p.add_argument("--val_freq", default=None, type=int)
    p.add_argument("--print_freq", default=None, type=int)
    # new surface (no reference equivalent)
    p.add_argument("--data-root", default=None)
    p.add_argument("--peak-lr", default=None, type=float,
                   help="override the hardcoded 1.6 post-warmup peak LR "
                        "(mix.py:181-198) — small archs/batches need less")
    p.add_argument("--max-iter", default=None, type=int,
                   help="override total iterations (smoke tests)")
    p.add_argument("--clip-grad", default=None, type=float,
                   help="global-norm gradient clipping (applied to the "
                        "fully reduced replicated gradients, so local "
                        "norms are exact)")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace of a few steps here")
    p.add_argument("--tensorboard", action="store_true",
                   help="also write TensorBoard event files next to the "
                        "JSONL scalars (reference mix.py:16,168-171)")
    p.add_argument("--mode", default="faithful",
                   choices=["faithful", "fast", "ring"],
                   help="faithful: bit-ordered quantized reduction; "
                        "fast: quantize->psum->dequantize; ring: ordered "
                        "quantized reduce-scatter/all-gather ring with "
                        "bit-packed eXmY wire (parallel/ring.py)")
    p.add_argument("--zero1", action="store_true",
                   help="ZeRO-1: shard the optimizer state 1/W over dp "
                        "(composes with --use_lars via zero1_lars, "
                        "round 5; parallel/zero.py)")
    p.add_argument("--zero2", action="store_true",
                   help="ZeRO-2: momentum AND the faithful reduction "
                        "sharded (all_to_all reduce-scatter; composes "
                        "with --use_lars).  --zero3 lives on the "
                        "ResNet-50 CLI (portable checkpoint layout)")
    from cpd_tpu.utils.config import (add_obs_flags,
                                      add_resilience_flags,
                                      add_transport_flags)
    add_resilience_flags(p)       # --fault-plan / guard / watchdog
    add_transport_flags(p)        # --overlap-reduce / --bucket-elems
    add_obs_flags(p)              # --obs-dir / --obs-flight
    return p


def main(argv=None) -> dict:
    args = build_parser().parse_args(argv)

    import jax
    import jax.numpy as jnp

    from cpd_tpu.data import CIFAR10Pipeline, load_cifar10
    from cpd_tpu.data.samplers import DistributedGivenIterationSampler
    from cpd_tpu.models import get_model
    from cpd_tpu.parallel.dist import (dist_init, host_batch_to_global,
                                       replicate)
    from cpd_tpu.parallel.mesh import data_parallel_mesh
    from cpd_tpu.train import (CheckpointManager, create_train_state,
                               make_eval_step, make_optimizer,
                               make_train_step, warmup_step_decay)
    from cpd_tpu.utils import (ProgressPrinter, ScalarWriter, StepProfiler,
                               format_validation_line, load_yaml_config,
                               merge_config_into_args)

    rank, world = dist_init() if args.dist else (0, 1)
    # after dist_init: it consults the resolved backend
    from cpd_tpu.utils import enable_compile_cache
    enable_compile_cache()
    explicit = {k: v for k, v in vars(args).items() if v is not None}
    merge_config_into_args(args, load_yaml_config(args.config),
                           cli_overrides=explicit)

    mesh = data_parallel_mesh()
    n_dev = mesh.devices.size
    seed = 24                                   # mix.py:23

    train_x, train_y, test_x, test_y = load_cifar10(args.data_root)
    dataset_len = len(train_y)

    # Schedule shape of mix.py:181-198: warmup 0.1 -> 1.6 over 5 epochs,
    # x0.1 after epochs 40 and 80; iters/epoch counts the emulated cluster.
    iter_per_epoch = math.ceil(
        dataset_len / (n_dev * args.batch_size * args.emulate_node))
    total_iter = args.max_epoch * iter_per_epoch
    if args.max_iter is not None:
        total_iter = args.max_iter
    peak_lr = args.peak_lr if args.peak_lr is not None else 1.6
    schedule = warmup_step_decay(
        peak_lr, 5 * iter_per_epoch,
        [40 * iter_per_epoch, 80 * iter_per_epoch],
        warmup_from=peak_lr / 16.0)

    model = get_model(args.arch)
    quant_opt = (args.opt_exp, args.opt_man) != (8, 23) or args.opt_kahan
    if quant_opt and args.use_lars:
        raise SystemExit("--use_lars and --opt_exp/--opt_man/--opt_kahan "
                         "are exclusive")
    if (args.opt_rounding != "nearest"
            and (args.opt_exp, args.opt_man) == (8, 23)):
        # quant_opt alone is not enough: --opt_kahan with an fp32 buffer
        # would silently drop SR (quant_sgd's (8,23) identity shortcut)
        raise SystemExit("--opt-rounding stochastic needs a quantized "
                         "buffer (--opt_exp/--opt_man below fp32)")
    shampoo_on = args.optimizer == "shampoo-lite"
    if shampoo_on:
        # the ShampooLite updater owns the optimizer math AND the
        # collective (reduce_in_update, like the ZeRO updaters) — the
        # optax-chain knobs cannot ride along
        if args.use_lars or quant_opt:
            raise SystemExit("--optimizer shampoo-lite is exclusive "
                             "with --use_lars and the quantized "
                             "momentum flags (--opt_exp/--opt_man/"
                             "--opt_kahan)")
        if args.clip_grad is not None:
            raise SystemExit("--clip-grad runs inside the optax chain, "
                             "which the ShampooLite updater bypasses")
        if args.overlap_reduce:
            raise SystemExit("--overlap-reduce does not compose with "
                             "--optimizer shampoo-lite (the updater "
                             "owns the collective; only the ZeRO-2 "
                             "updater has a tap hook)")
        if args.bucket_elems is not None:
            raise SystemExit("--bucket-elems does not compose with "
                             "--optimizer shampoo-lite: the step hands "
                             "the updater its quant kwargs without the "
                             "bucket layout, so the requested bucketed "
                             "transport would silently never run")
    if not shampoo_on and (
            (args.shampoo_stat_exp, args.shampoo_stat_man) != (8, 23)
            or args.shampoo_stat_mode != "ring"):
        # same loud-rejection rule as --opt_exp below: statistics-format
        # flags without the optimizer that consumes them must not
        # silently vanish
        raise SystemExit("--shampoo-stat-exp/--shampoo-stat-man/"
                         "--shampoo-stat-mode need --optimizer "
                         "shampoo-lite; any other optimizer would "
                         "silently ignore them")
    if args.optimizer not in ("auto", "shampoo-lite"):
        if args.use_lars and args.optimizer != "lars":
            raise SystemExit("--use_lars contradicts --optimizer "
                             f"{args.optimizer}")
        if quant_opt and args.optimizer != "quant_sgd":
            # under 'auto' these flags SELECT quant_sgd; an explicit
            # other optimizer would silently drop them — the numerics
            # the user asked for must not vanish without a word
            raise SystemExit(f"--opt_exp/--opt_man/--opt_kahan need "
                             f"the quantized momentum buffer; "
                             f"--optimizer {args.optimizer} would "
                             f"ignore them (use quant_sgd or auto)")
    opt_name = (args.optimizer if args.optimizer not in ("auto",
                                                         "shampoo-lite")
                else "lars" if args.use_lars else
                "quant_sgd" if quant_opt else "sgd")
    tx = make_optimizer(opt_name, schedule, momentum=args.momentum,
                        weight_decay=args.weight_decay,
                        opt_exp=args.opt_exp, opt_man=args.opt_man,
                        opt_kahan=args.opt_kahan,
                        opt_rounding=args.opt_rounding,
                        opt_seed=args.opt_seed,
                        clip_norm=args.clip_grad)
    # Resilience stack (docs/RESILIENCE.md).  This trainer wires the
    # in-step defenses (guard + injected gradient faults), the host
    # faults, the watchdog, and the divergence STOP; checkpoint-rollback
    # recovery lives on the LM trainer, whose synchronous batch fetch
    # can rewind (the Prefetcher pipeline here cannot).
    from cpd_tpu.utils.config import build_resilience
    res = build_resilience(args, n_steps=total_iter, rank=rank,
                           world=n_dev)
    if res["wraps_optimizer"] and (args.zero1 or args.zero2
                                   or shampoo_on):
        # watchdog / sentinel / host-level faults compose fine with ZeRO
        # and Shampoo-lite; only the optimizer WRAPPERS (guard,
        # grad-fault injection) don't
        raise SystemExit("--guard-grads / grad_* faults do not compose "
                         "with the ZeRO/ShampooLite updaters (custom "
                         "update_fn owns the optimizer math the guard "
                         "would wrap)")
    if res["verify"] and (args.zero1 or args.zero2 or shampoo_on):
        raise SystemExit("--verify-reduce needs the step's own reduction "
                         "and a donate-free state for discard-and-retry; "
                         "the ZeRO/ShampooLite updaters own the "
                         "collective (reduce_in_update) — run without "
                         "--zero1/--zero2/--optimizer shampoo-lite")
    if res["quant_stats"] and (args.zero1 or args.zero2 or shampoo_on):
        raise SystemExit("--precision-ladder/--quant-telemetry need the "
                         "step's own reduction for the wire telemetry; "
                         "the ZeRO/ShampooLite updaters own the "
                         "collective (reduce_in_update) — run without "
                         "--zero1/--zero2/--optimizer shampoo-lite")
    # ISSUE 12 lifted the PR 8 fail-fasts: --bucket-elems/--overlap-reduce
    # compose with --zero1 (the update slices the step's fully-reduced
    # grads) AND --zero2 (zero2_sgd(bucket_elems=...) adopts the bucketed
    # flat layout and its make_tap_reduce hook runs the per-bucket
    # reduce-scatter inside the backward taps); --overlap-reduce also
    # composes with --emulate_node > 1 (the unrolled micro chain feeds
    # the last micro-batch's taps); --block-scale composes with --zero2
    # (the faithful all_to_all carries the blocked wire).
    if args.block_scale and args.mode != "ring" and not args.zero2:
        raise SystemExit("--block-scale needs --mode ring (or --zero2, "
                         "whose all_to_all carries the blocked wire): "
                         "the per-block scale sidecar rides a packed "
                         "wire")
    if args.block_scale and args.grad_man < 2:
        raise SystemExit(f"--block-scale needs a packable gradient format "
                         f"(man_bits >= 2 for the codec's special codes), "
                         f"got e{args.grad_exp}m{args.grad_man}")
    if res["active"]:
        tx = res["wrap_tx"](tx, axis_name="dp")
    injector, watchdog = res["injector"], res["watchdog"]
    sentinel, meter = res["sentinel"], res["meter"]
    psup = res["precision"]
    esup = res["elastic"]
    # observability spine (docs/OBSERVABILITY.md): pure host-side
    # observation — step outputs bitwise identical with or without
    # --obs-dir (pinned by the obs-smoke gate).  The data span lives on
    # the Prefetcher's producer thread, so this trainer traces only the
    # step/validate/checkpoint phases it runs on the main thread.
    from cpd_tpu.obs import NULL_TRACER
    from cpd_tpu.utils.config import build_obs
    obs = build_obs(args, run="resnet18",
                    meta={"mode": args.mode,
                          "grad_format": [args.grad_exp,
                                          args.grad_man]})
    otr = obs["tracer"] if obs["tracer"] is not None else NULL_TRACER
    oreg, oflight = obs["registry"], obs["flight"]
    if watchdog is not None and oflight is not None:
        watchdog.on_trip = lambda ctx: oflight.dump("watchdog")

    def run_meta():
        # ladder state rides every checkpoint's metadata sidecar so a
        # restart resumes AT the escalated format (docs/RESILIENCE.md
        # "Precision ladder"); the elastic fleet view rides along so a
        # process restart resumes with the same alive set (ISSUE 19)
        meta = {}
        if psup is not None:
            meta["precision"] = psup.state_dict()
        if esup is not None:
            meta["elastic"] = esup.state_dict()
        return meta or None

    state = create_train_state(model, tx, jnp.zeros((2, 32, 32, 3)),
                               jax.random.PRNGKey(seed))
    zero = None
    shampoo = None
    if shampoo_on:
        if args.zero1 or args.zero2:
            raise SystemExit("--optimizer shampoo-lite and --zero1/"
                             "--zero2 are mutually exclusive (one "
                             "custom updater per step)")
        from cpd_tpu.train import shampoo_lite
        shampoo = shampoo_lite(
            schedule, world=n_dev, momentum=args.momentum,
            weight_decay=args.weight_decay,
            stat_exp=args.shampoo_stat_exp,
            stat_man=args.shampoo_stat_man,
            stat_mode=args.shampoo_stat_mode)
        state = state.replace(opt_state=shampoo.init(state.params))
    if args.zero1 and args.zero2:
        raise SystemExit("--zero1/--zero2 are mutually exclusive")
    if args.zero1 or args.zero2:
        if quant_opt:
            raise SystemExit("--zero1/--zero2 do not compose with the "
                             "quantized optimizer state (the ZeRO "
                             "updaters carry fp32 flat momentum)")
        if args.clip_grad is not None:
            raise SystemExit("--clip-grad runs inside the optax chain, "
                             "which the ZeRO updaters bypass")
        if args.zero2 and args.mode != "faithful":
            raise SystemExit("--zero2 shards the faithful reduction; "
                             "--mode fast is not supported with it")
        from cpd_tpu.parallel import zero as zero_mod
        maker = getattr(zero_mod,
                        ("zero1" if args.zero1 else "zero2")
                        + ("_lars" if args.use_lars else "_sgd"))
        # world = the dp axis size (emulate_node replicas live INSIDE a
        # rank's micro-batch scan, same as the resnet50 CLI's wiring).
        # ZeRO-2 adopts the bucketed flat layout when --bucket-elems is
        # set, so the overlap taps and the update consume the SAME
        # per-bucket shards (zero2_sgd's make_tap_reduce, ISSUE 12)
        zkw = dict(momentum=args.momentum,
                   weight_decay=args.weight_decay)
        if args.zero2:
            zkw["bucket_elems"] = args.bucket_elems
        zero = maker(schedule, world=n_dev, **zkw)
        state = state.replace(opt_state=zero.init(state.params))
    ckpt_dir = os.path.abspath(args.save_path)
    manager = CheckpointManager(ckpt_dir, track_best=True,
                                integrity=getattr(args, "ckpt_integrity",
                                                  True))
    start_iter = 0
    if args.init_from_torch and args.load_path:
        raise SystemExit("--init-from-torch and --load-path are exclusive")
    if args.export_torch and args.arch != "res_cifar":
        # fail in milliseconds, not after the training run: only the
        # reference CIFAR ResNet-18 has a torch key map
        raise SystemExit(f"--export-torch supports --arch res_cifar only "
                         f"(got --arch {args.arch})")
    if args.init_from_torch:
        # Migration path: continue training / evaluate a model trained by
        # the torch reference (docs/MIGRATING.md).  Params + BN running
        # stats come from the .pth; optimizer state starts fresh.  Takes
        # the same precedence --load-path has: auto-resume from save_path
        # must NOT silently overwrite an explicitly requested import.
        from cpd_tpu.interop import (assert_compatible,
                                     import_reference_resnet18_cifar,
                                     load_reference_checkpoint)
        sd = load_reference_checkpoint(args.init_from_torch)
        converted = import_reference_resnet18_cifar(sd)
        assert_compatible(converted, {"params": state.params,
                                      "batch_stats": state.batch_stats})
        state = state.replace(params=converted["params"],
                              batch_stats=converted["batch_stats"])
        if rank == 0:
            print(f"=> imported torch checkpoint {args.init_from_torch}")
    elif args.load_path:
        # Warm-start from an explicit checkpoint dir (mix.py --load-path /
        # train_util.load_state:274-318); --resume-opt additionally restores
        # the optimizer state and step counter, else params only.
        from cpd_tpu.train import restore_latest
        tmpl = zero.portable_template(state) if zero else state
        loaded = restore_latest(os.path.abspath(args.load_path), tmpl)
        if loaded is None:
            raise FileNotFoundError(
                f"--load-path {args.load_path}: no checkpoint found")
        if args.resume_opt:
            state = zero.import_state(loaded) if zero else loaded
            start_iter = int(loaded.step)
        else:
            state = state.replace(params=loaded.params,
                                  batch_stats=loaded.batch_stats)
        if rank == 0:
            print(f"=> loaded {args.load_path} "
                  f"(opt {'restored' if args.resume_opt else 'fresh'})")
    elif manager.latest_step() is not None:
        # ZeRO checkpoints are saved in the PORTABLE layout (pad-trimmed
        # momentum), so they restore at any device count
        restored = manager.restore(
            zero.portable_template(state) if zero else state)
        if restored is not None:
            state = zero.import_state(restored) if zero else restored
            start_iter = int(restored.step)
            if rank == 0:
                print(f"=> resumed from iter {start_iter}")
            if psup is not None:
                # resume the ladder where the checkpoint left it — the
                # acceptance contract: a restart mid-escalation runs at
                # the escalated format, not home
                meta = manager.metadata()
                if meta and meta.get("precision"):
                    psup.load_state_dict(meta["precision"])
                    if rank == 0:
                        print(f"=> resumed precision ladder at "
                              f"{psup.name}"
                              + (" (escalated)" if psup.escalated
                                 else ""))
    # orbax restores arrays committed to a single device; the train step's
    # shard_map needs the state laid out over the mesh (replicated, except
    # the ZeRO momentum which is dp-sharded)
    if shampoo is not None:
        state, extra = shampoo.mesh_layout(state, mesh)
        to_ckpt = shampoo.export_state
    elif zero is None:
        state = replicate(state, mesh)
        extra = {}
        to_ckpt = lambda st: st                               # noqa: E731
    else:
        state, extra = zero.mesh_layout(state, mesh)
        to_ckpt = zero.export_state

    from cpd_tpu.utils.config import block_key, overlap_key
    ov_key = overlap_key(args)
    bk_key = block_key(args)
    step_kw = dict(emulate_node=args.emulate_node, use_aps=args.use_APS,
                   use_kahan=args.use_kahan,
                   grad_rounding=args.grad_rounding,
                   grad_seed=args.grad_seed,
                   quant_stats=res["quant_stats"],
                   sat_fault_plan=res["sat_plan"],
                   overlap_reduce=args.overlap_reduce,
                   bucket_elems=args.bucket_elems, **extra)
    supervisor = res["supervisor"]
    resync_fn = None
    if supervisor is not None or psup is not None:
        # one or both ladders (docs/RESILIENCE.md "Degraded transports" /
        # "Precision ladder"): lazily compiled steps keyed by
        # `ladder_step_key` — transport level, eXmY format, or the
        # (level, format) pair when both supervisors run
        from cpd_tpu.resilience import (StepTable, ladder_step_key,
                                        level_reduce_kwargs)
        from cpd_tpu.resilience.precision import resolve_ladder_key
        if supervisor is not None:
            from cpd_tpu.parallel.integrity import make_consensus_fns
            _, resync_fn = make_consensus_fns(mesh, "dp")

        def build_step(key):
            level, fmt = resolve_ladder_key(
                key, transport_on=supervisor is not None,
                precision_on=psup is not None, level=args.mode,
                fmt=(args.grad_exp, args.grad_man),
                overlap_on=ov_key is not None,
                block_on=bk_key is not None)
            if supervisor is not None:
                rkw = level_reduce_kwargs(level, *fmt)
            else:
                rkw = dict(mode=level, grad_exp=fmt[0], grad_man=fmt[1])
            # block scaling only exists on the ring rung at a packable
            # format: a transport downgrade (faithful/fp32) or a
            # precision escalation to (8, 23) retraces WITHOUT the
            # sidecar wire — rung validity beats knob persistence
            blk = (args.block_scale and rkw.get("mode") == "ring"
                   and fmt[1] >= 2 and fmt != (8, 23))
            return make_train_step(
                model, tx, mesh, donate=False,
                verify_reduce=res["verify"],
                wire_fault_plan=(res["wire_plan"] if level == "ring"
                                 else None),
                block_scale=blk, block_size=args.block_size,
                **rkw, **step_kw)

        step_table = StepTable(build_step)
        train_step = step_table[ladder_step_key(supervisor, psup,
                                                overlap=ov_key,
                                                block=bk_key)]
    else:
        # no ladder (verify off, or a non-ladder mode like fast):
        # verification, when on, is detection-only agreement checking
        step_table = None
        train_step = make_train_step(
            model, tx, mesh, grad_exp=args.grad_exp,
            grad_man=args.grad_man, mode=args.mode,
            verify_reduce=res["verify"],
            wire_fault_plan=res["wire_plan"],
            block_scale=args.block_scale, block_size=args.block_size,
            **step_kw)
    eval_step = make_eval_step(model, mesh)

    # Global per-step batch = per-chip batch x chips x emulated nodes
    # (mix.py:123-132 scales max_iter by emulate_node instead; same
    # cluster).  Each host loads its 1/world contiguous slice; the sampler
    # hands out per-host index blocks (train_util.py:212-215) and
    # host_batch_to_global stitches them into the sharded global array.
    global_batch = args.batch_size * n_dev * args.emulate_node
    host_batch = global_batch // world
    pipeline = CIFAR10Pipeline(train_x, train_y, host_batch, augment=True,
                               cutout=0)
    eval_bs = max(n_dev, (min(1000, len(test_y)) // n_dev) * n_dev)
    eval_host = eval_bs // world
    eval_pipe = CIFAR10Pipeline(test_x, test_y, eval_bs, augment=False)

    def validate(step_no: int) -> dict:
        tot = {"loss": 0.0, "top1": 0.0, "top5": 0.0}
        n_batches = 0
        limit = (len(test_y) // eval_bs) * eval_bs
        for lo in range(0, limit, eval_bs):
            sel = np.arange(lo + rank * eval_host,
                            lo + (rank + 1) * eval_host)
            x, y = eval_pipe.batch(sel)
            m = eval_step(state, host_batch_to_global(x, mesh),
                          host_batch_to_global(y, mesh))
            for k in tot:
                tot[k] += float(m[k])
            n_batches += 1
        avg = {k: v / max(n_batches, 1) for k, v in tot.items()}
        if rank == 0:
            print(format_validation_line(avg["loss"], 100 * avg["top1"],
                                         100 * avg["top5"]), flush=True)
        return avg

    def export_torch(state) -> None:
        if not args.export_torch:
            return
        from cpd_tpu.interop import (export_reference_resnet18_cifar,
                                     save_torch_checkpoint)
        host = jax.device_get({"params": state.params,
                               "batch_stats": state.batch_stats})
        try:
            sd = export_reference_resnet18_cifar(host)
        except KeyError as e:
            raise SystemExit(
                f"--export-torch supports the res_cifar layout only "
                f"(--arch {args.arch} has no reference key map): {e}")
        if rank == 0:
            save_torch_checkpoint(sd, args.export_torch)
            print(f"=> exported torch checkpoint {args.export_torch}")

    if args.evaluate:                            # mix.py:-e
        res = validate(start_iter)
        export_torch(state)
        return res

    sampler = DistributedGivenIterationSampler(
        dataset_len, total_iter, host_batch, world_size=world, rank=rank,
        seed=0, last_iter=start_iter - 1)
    writer = ScalarWriter(os.path.join(ckpt_dir, "logs"), rank=rank,
                          tensorboard=args.tensorboard)
    progress = ProgressPrinter(total_iter, args.print_freq, rank=rank)
    best_prec1 = 0.0
    last = {"loss": float("nan"), "accuracy": 0.0}
    step_no = start_iter
    profiler = StepProfiler(args.profile_dir, start=start_iter + 2)
    t0 = now()
    def produced():
        # host-side batch prep (augmentation runs in the native threaded
        # executor) on a background thread, 2 steps ahead of the device
        s = step_no
        for batch_idx in sampler.batches():
            x, y = pipeline.batch(batch_idx, seed=s // iter_per_epoch)
            yield (host_batch_to_global(x, mesh),
                   host_batch_to_global(y, mesh))
            s += 1

    # SIGTERM (spot-VM preemption) → save at the next step boundary and
    # exit; the iteration-based sampler resumes at exactly this step via
    # last_iter (train_util.py:159-222 semantics), so nothing re-trains.
    from cpd_tpu.train import PreemptionGuard, loss_diverged, preempt_save
    from cpd_tpu.resilience.inject import InjectedPreemption
    guard = PreemptionGuard()
    preempted = False
    diverged = False
    prev_batch = None
    # --- elastic training setup (ISSUE 19): detection + drain only —
    # the prefetcher pipeline cannot rewind a batch, so this trainer's
    # recovery doctrine is a clean drain-save and a controlled exit
    # (the in-run shrink lives on the LM trainer and run_elastic)
    elastic_table, elastic_links, last_dt = None, {}, None
    if esup is not None:
        if res["plan"] is not None and res["plan"].elastic_faults():
            from cpd_tpu.resilience.elastic import heartbeat_table
            elastic_table = heartbeat_table(res["plan"],
                                            esup.home_world, total_iter)
            elastic_links = {f.step: (int(f.arg) if f.arg >= 0 else 0,
                                      int(f.arg2) if f.arg2 >= 0 else 1)
                             for f in res["plan"].elastic_faults()
                             if f.kind == "link_flaky"}
    from cpd_tpu.utils.prefetch import Prefetcher
    batches = Prefetcher(produced(), depth=2)
    batch_iter = iter(batches)
    try:
        for gx, gy in batch_iter:
            if watchdog is not None and watchdog.tripped:
                # trip interrupt absorbed by the SIGINT-trapping guard;
                # honor it at the boundary (docs/RESILIENCE.md)
                watchdog.disarm()     # acknowledge: cancels hard-exit
                meter.bump("watchdog_trips")
                preempt_save(manager, step_no, to_ckpt(state), rank,
                             metadata=run_meta(), what="watchdog stop at")
                preempted = True
                break
            if guard.should_stop():      # collective when multi-host
                if oflight is not None:
                    oflight.dump("preempt")
                preempt_save(manager, step_no, to_ckpt(state), rank,
                             metadata=run_meta())
                preempted = True
                break
            profiler.step(step_no)
            # --- elastic supervision (ISSUE 19): one heartbeat row per
            # update (plan-derived in drills, the measured step time
            # standing in for every dp host otherwise); any drain
            # decision -> sealed checkpoint + controlled exit
            if esup is not None:
                if elastic_table is not None:
                    row = (elastic_table[step_no]
                           if step_no < len(elastic_table)
                           else [1.0] * esup.home_world)
                elif last_dt is not None:
                    row = [last_dt] * esup.home_world
                else:
                    row = None
                decision = (esup.on_heartbeats(step_no, row)
                            if row is not None else None)
                meter.counts["elastic_hot_steps"] = \
                    esup.counters["hot_steps"]
                meter.counts["elastic_heartbeat_misses"] = \
                    esup.counters["heartbeat_misses"]
                if decision is None and step_no in elastic_links:
                    host, attempts = elastic_links.pop(step_no)
                    for _ in range(attempts):
                        act = esup.on_link_failure(step_no, host)
                        if act == "shrink":
                            decision = ("shrink", (host,))
                            meter.bump("elastic_link_escalations")
                            break
                        meter.bump("elastic_link_retries")
                    else:
                        esup.on_step_ok(step_no)
                        if rank == 0 and attempts:
                            print(f"=> elastic: flaky link into host "
                                  f"{host} at iter {step_no + 1} "
                                  f"absorbed by {attempts} in-step "
                                  f"retr"
                                  f"{'y' if attempts == 1 else 'ies'}",
                                  file=sys.stderr)
                if decision is not None and decision[0] == "shrink":
                    for _ in decision[1]:
                        meter.bump("elastic_drains")
                    meter.bump("elastic_shrinks")
                    if rank == 0:
                        print(f"=> elastic: host(s) "
                              f"{list(decision[1])} unhealthy at iter "
                              f"{step_no + 1} — draining to a sealed "
                              f"checkpoint and stopping (in-run "
                              f"shrink: LM trainer / run_elastic)",
                              file=sys.stderr)
                    if oflight is not None:
                        oflight.dump("elastic")
                    preempt_save(manager, step_no, to_ckpt(state), rank,
                                 metadata=run_meta(),
                                 what="elastic drain at")
                    preempted = True
                    break
            try:
                if injector is not None:
                    injector.maybe_preempt(step_no)
                    action = injector.batch_action(step_no)
                    if action == "drop":
                        # this batch never arrives; train on the next
                        # one (same semantics as run_guarded / lm)
                        meter.bump("batches_dropped")
                        try:
                            gx, gy = next(batch_iter)
                        except StopIteration:
                            break
                    if action == "dup" and prev_batch is not None:
                        meter.bump("batches_duplicated")
                        gx, gy = prev_batch
                    gx, gy = injector.corrupt_batch(step_no, (gx, gy))
                prev_batch = (gx, gy)
                if watchdog is not None:
                    watchdog.arm(step_no, loss=last.get("loss"))
                if injector is not None:
                    injector.maybe_stall(step_no)
                prev_state = state    # verified-reduce discard target
                t_step = now()
                with otr.span("step", step=step_no + 1):
                    state, metrics = train_step(state, gx, gy)
                    last = {k: float(v)
                            for k, v in metrics.items()}  # sync
                last_dt = now() - t_step
                if esup is not None:
                    esup.on_step_ok(step_no)
                if watchdog is not None:
                    watchdog.disarm()
            except KeyboardInterrupt:
                if watchdog is not None and watchdog.tripped:
                    watchdog.disarm()     # acknowledge: cancels hard-exit
                    meter.bump("watchdog_trips")
                    preempt_save(manager, step_no, to_ckpt(state), rank,
                                 metadata=run_meta(),
                                 what="watchdog stop at")
                    preempted = True
                    break
                raise
            except InjectedPreemption:
                meter.bump("preemptions")
                if oflight is not None:
                    oflight.dump("preempt")
                preempt_save(manager, step_no, to_ckpt(state), rank,
                             metadata=run_meta(), what="injected preemption at")
                preempted = True
                break
            # --- verified-reduce supervision (ISSUE 4) ----------------
            # reduce_ok == 0: this step's reduce failed its checksums /
            # agreement — discard the corrupted update (state rewinds to
            # the pre-step pytree; steps are built donate=False) and let
            # the supervisor walk the ring -> faithful -> fp32 ladder.
            # Unlike run_guarded, the prefetcher pipeline cannot rewind
            # a batch, so a "retry" trains the NEXT batch at the same
            # rung — the update index (state.step) did not advance, so a
            # deterministic injected fault still re-fires and drives the
            # downgrade exactly as in the harness loop.
            if supervisor is None and res["verify"] and float(
                    last.get("reduce_ok", 1.0)) == 0.0:
                # non-ladder mode (fast): detection only — count + warn
                meter.bump("wire_faults_detected")
                if rank == 0:
                    print(f"=> reduce verify FAILED at iter "
                          f"{step_no + 1} (mode {args.mode} has no "
                          f"transport ladder: detection only)",
                          file=sys.stderr)
            if supervisor is not None and float(
                    last.get("reduce_ok", 1.0)) == 0.0:
                meter.bump("wire_faults_detected")
                state = prev_state
                action = supervisor.on_failure(step_no)
                if action == "give_up":
                    if rank == 0:
                        print(f"=> verified reduce failed at the fp32 "
                              f"transport floor (iter {step_no + 1}) — "
                              f"not a wire problem; stopping",
                              file=sys.stderr)
                    diverged = True
                    break
                if action == "downgrade":
                    meter.bump("transport_downgrades")
                    state = resync_fn(state)
                    meter.bump("resyncs")
                    train_step = step_table[ladder_step_key(supervisor,
                                                            psup,
                                                            overlap=ov_key,
                                                            block=bk_key)]
                    if rank == 0:
                        print(f"=> wire fault detected at iter "
                              f"{step_no + 1} (hop_bad "
                              f"{int(last.get('reduce_hop_bad', 0))}, "
                              f"gather_bad "
                              f"{int(last.get('reduce_gather_bad', 0))})"
                              f" — transport downgraded to "
                              f"{supervisor.mode}, replicas re-synced "
                              f"from rank 0", file=sys.stderr)
                else:
                    meter.bump("reduce_retries")
                    if rank == 0:
                        print(f"=> wire fault detected at iter "
                              f"{step_no + 1} — update discarded, "
                              f"retrying on the {supervisor.mode} "
                              f"transport", file=sys.stderr)
                continue
            if supervisor is not None and \
                    supervisor.on_success(step_no) == "upgrade":
                meter.bump("transport_upgrades")
                train_step = step_table[ladder_step_key(supervisor,
                                                            psup,
                                                            overlap=ov_key,
                                                            block=bk_key)]
                if rank == 0:
                    print(f"=> transport probation passed at iter "
                          f"{step_no + 1}: back to {supervisor.mode}",
                          file=sys.stderr)
            step_no += 1
            meter.observe_metrics(last)
            if oreg is not None:
                oreg.absorb_step_metrics(last, step_no)
            if oflight is not None:
                oflight.record("step", step=step_no,
                               loss=last["loss"])
            # --- precision-ladder supervision (ISSUE 5) ---------------
            # host decision on the psum-agreed prec_wire_* telemetry;
            # escalation re-formats the NEXT step (the update that
            # tripped the detector was already guarded in-step)
            if psup is not None:
                from cpd_tpu.resilience import ladder_step_key
                pact = psup.on_metrics(step_no - 1, last)
                if psup.last_hot:
                    meter.bump("sat_hot_steps")
                if pact is not None:
                    meter.bump("precision_escalations"
                               if pact == "escalate"
                               else "precision_deescalations")
                    train_step = step_table[ladder_step_key(supervisor,
                                                            psup,
                                                            overlap=ov_key,
                                                            block=bk_key)]
                    if rank == 0:
                        how = ("escalated" if pact == "escalate"
                               else "probation passed: back")
                        print(f"=> precision ladder {how} to "
                              f"{psup.name} at iter {step_no} "
                              f"(sat {int(last.get('prec_wire_sat', 0))}"
                              f"/{int(last.get('prec_wire_total', 0))}"
                              f" nan "
                              f"{int(last.get('prec_wire_nan', 0))})",
                              file=sys.stderr)
            if injector is not None:
                # step_no - 1 == the 0-based update index this loss came
                # from — the same clock the pre-step hooks above use
                last["loss"] = injector.fault_loss(step_no - 1,
                                                   last["loss"])
            # a guard-skipped step's loss metric may be poisoned by the
            # bad batch/grads; the anomaly was already handled in-step
            guard_ok = float(last.get("guard_ok", 1.0)) != 0.0
            if (sentinel is not None and guard_ok
                    and sentinel.update(last["loss"])):
                # divergence STOP (rollback recovery: LM trainer / the
                # resilience.run_guarded loop)
                if rank == 0:
                    print(f"=> divergence sentinel tripped at iter "
                          f"{step_no} (loss {last['loss']:.4g})",
                          file=sys.stderr)
                diverged = True
                break
            if (sentinel is None and guard_ok
                    and loss_diverged(last["loss"],
                                      f"iter {step_no}", rank)):
                diverged = True
                break
            progress.maybe_print(step_no, _suffix=meter.suffix(),
                                 Loss=last["loss"],
                                 Prec=100 * last["accuracy"],
                                 LR=float(schedule(step_no)))
            writer.add_scalar("train/loss", last["loss"], step_no)
            writer.add_scalar("train/acc", last["accuracy"], step_no)
            if step_no % args.val_freq == 0 or step_no == total_iter:
                with otr.span("validate", step=step_no):
                    val = validate(step_no)
                writer.add_scalar("val/top1", val["top1"], step_no)
                prec1 = 100 * val["top1"]
                best_prec1 = max(best_prec1, prec1)
                with otr.span("checkpoint", step=step_no):
                    manager.save(step_no, to_ckpt(state),
                                 best_metric=prec1,
                                 metadata=run_meta())
                if injector is not None:
                    # the fault must land on the FINAL bytes — without
                    # integrity the save is still async at this point
                    manager.wait()
                if injector is not None and injector.corrupt_checkpoint(
                        step_no, manager.directory) and rank == 0:
                    print(f"=> injected checkpoint corruption at step "
                          f"{step_no}", file=sys.stderr)
    finally:
        guard.uninstall()
        if watchdog is not None:
            watchdog.close()
        batches.close()   # stop the producer even on an exception path
        # close() stops an in-flight jax.profiler trace even when the
        # loop died inside the window (watchdog interrupt, injected
        # fault) — leaking a running trace poisons every later
        # start_trace in this process (ISSUE 11 satellite)
        profiler.close()
    from cpd_tpu.resilience import report_unfired
    if esup is not None and res["plan"] is not None:
        # the elastic harness owns its kinds' accounting: anything
        # scheduled past the last processed update, or aimed outside
        # the fleet, never manifested (mirrors run_elastic / lm)
        leftover = sorted(
            f for f in res["plan"].elastic_faults()
            if f.step >= step_no or int(max(f.arg, 0)) >= esup.home_world)
        if leftover:
            meter.bump("faults_unfired", len(leftover))
            if rank == 0:
                print(f"=> elastic plan: {len(leftover)} spec(s) never "
                      f"fired: {leftover}", file=sys.stderr)
    # wire faults only fire when a ring-mode step baked the table in —
    # a wire_* spec on a gather/psum run must read as UNFIRED, not pass
    report_unfired(injector, n_steps=total_iter, meter=meter, rank=rank,
                   wire_armed=(supervisor.home == "ring"
                               if supervisor is not None
                               else args.mode == "ring"),
                   host_armed=esup is not None)
    manager.wait()
    writer.close()
    if rank == 0 and not (preempted or diverged):  # interrupted != "done"
        print(f"done: {step_no - start_iter} iters in {now()-t0:.1f}s "
              f"best Prec@1 {best_prec1:.2f}")
    manager.close()
    if not (preempted or diverged):
        export_torch(state)
    from cpd_tpu.utils.config import finish_obs
    obs_out = finish_obs(obs, meter=meter, last=last, step_no=step_no,
                         supervisor=supervisor, precision=psup,
                         elastic=esup, rank=rank, preempted=preempted,
                         diverged=diverged)
    return {"step": step_no, "best_prec1": best_prec1,
            "diverged": diverged,
            **({"resilience": meter.as_dict()} if res["active"] else {}),
            **({"obs": obs_out} if obs_out is not None else {}),
            **last}


if __name__ == "__main__":
    res = main()
    sys.exit(3 if res.get("diverged") else 0)
