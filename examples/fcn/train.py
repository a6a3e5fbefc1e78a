"""FCN-R50-d8 segmentation trainer — the reference's fourth workload,
in-repo instead of the mmcv-fork hack (README.md:132-150: forks of mmcv
branch APS_support + mmsegmentation, precision toggled by editing
optimizer.py line 27).  Here precision is just flags on the shared trainer,
proving the framework integration point the reference's fork demonstrates:
the quantized all-reduce wraps any model's gradients.

Iteration-based like mmseg (40K iters at crop 769; README.md:133).
"""

from __future__ import annotations

import argparse
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
    p = argparse.ArgumentParser(description="cpd_tpu FCN/Cityscapes")
    p.add_argument("--crop-size", default=769, type=int)
    p.add_argument("--num-classes", default=19, type=int)
    p.add_argument("--batch-size", default=2, type=int,
                   help="per chip (mmseg default: 2 imgs/GPU)")
    p.add_argument("--max-iter", default=40000, type=int)
    p.add_argument("--base-lr", default=0.01, type=float)
    p.add_argument("--momentum", default=0.9, type=float)
    p.add_argument("--wd", default=0.0005, type=float)
    p.add_argument("--print-freq", default=50, type=int)
    p.add_argument("--save-path", default="fcn_ckpt")
    p.add_argument("--val-freq", default=4000, type=int)
    p.add_argument("--ckpt-freq", default=4000, type=int,
                   help="checkpoint interval (mmcv CheckpointHook parity)")
    # precision flags — the reference's edit-a-source-line, as real flags
    p.add_argument("--grad_exp", default=8, type=int)
    p.add_argument("--grad_man", default=23, type=int)
    p.add_argument("--use_APS", action="store_true")
    p.add_argument("--use_kahan", action="store_true")
    p.add_argument("--emulate_node", default=1, type=int)
    p.add_argument("--mode", default="faithful",
                   choices=["faithful", "fast", "ring"])
    p.add_argument("--dist", action="store_true")
    p.add_argument("--data-root", default=None,
                   help="Cityscapes root (leftImg8bit/gtFine); synthetic "
                        "fallback when absent")
    p.add_argument("--synthetic-size", default=256, type=int)
    p.add_argument("--tiny-backbone", action="store_true",
                   help="1-block-per-stage backbone (smoke tests)")
    p.add_argument("--tensorboard", action="store_true",
                   help="also write TensorBoard event files next to the "
                        "JSONL scalars (reference mix.py:16,168-171)")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace of a few steps here")
    p.add_argument("--aux-head", action="store_true",
                   help="auxiliary FCN head on stage-3 features at loss "
                        "weight 0.4 (mmseg fcn_r50-d8 default)")
    p.add_argument("--aux-weight", default=0.4, type=float)
    return p


def main(argv=None) -> dict:
    args = build_parser().parse_args(argv)

    import jax
    import jax.numpy as jnp

    from cpd_tpu.data.segmentation import load_segmentation
    from cpd_tpu.models import fcn_r50_d8
    from cpd_tpu.parallel.dist import dist_init, host_batch_to_global
    from cpd_tpu.parallel.mesh import data_parallel_mesh
    from cpd_tpu.train import (create_train_state, make_optimizer,
                               make_train_step)
    from cpd_tpu.train.step import seg_cross_entropy_loss, seg_loss_with_aux
    from cpd_tpu.train.schedules import piecewise_linear
    from cpd_tpu.utils import ProgressPrinter, ScalarWriter, StepProfiler

    rank, world = dist_init() if args.dist else (0, 1)
    # after dist_init: it consults the resolved backend
    from cpd_tpu.utils import enable_compile_cache
    enable_compile_cache()
    mesh = data_parallel_mesh()
    n_dev = mesh.devices.size

    # real Cityscapes (leftImg8bit/gtFine tree, 769x769 crops — the mmseg
    # fcn_r50-d8 pipeline the reference trains on, README.md:132-150) when
    # --data-root points at one; synthetic stand-in otherwise
    ds = load_segmentation(args.data_root, crop_size=args.crop_size,
                           num_classes=args.num_classes,
                           synthetic_size=args.synthetic_size)
    # validation split: real Cityscapes val/ when present; otherwise (no
    # val/ tree, or fully synthetic data) evaluate on the training
    # distribution at deterministic crops — never mix real train with
    # synthetic val
    try:
        val_ds = load_segmentation(args.data_root, split="val",
                                   crop_size=args.crop_size,
                                   num_classes=args.num_classes,
                                   synthetic_size=args.synthetic_size,
                                   flip=False)
        if type(val_ds) is not type(ds):
            val_ds = ds
    except FileNotFoundError:
        val_ds = ds
    global_batch = args.batch_size * n_dev * args.emulate_node

    # mmseg's poly schedule ~ piecewise-linear decay to lr*0.01 at max_iter
    schedule = piecewise_linear([0, args.max_iter],
                                [args.base_lr, args.base_lr * 0.01])
    tiny = ({"stage_sizes": (1, 1, 1, 1), "head_channels": 64}
            if args.tiny_backbone else {})
    model = fcn_r50_d8(num_classes=args.num_classes, dtype=jnp.bfloat16,
                       aux_head=args.aux_head, **tiny)
    tx = make_optimizer("sgd", schedule, momentum=args.momentum,
                        weight_decay=args.wd)
    state = create_train_state(
        model, tx, jnp.zeros((1, args.crop_size, args.crop_size, 3)),
        jax.random.PRNGKey(0))

    # interval checkpoints + auto-resume — the mmcv runner's
    # CheckpointHook/resume behavior the reference relies on
    # (README.md:132-150); restored arrays are re-replicated over the mesh
    from cpd_tpu.parallel.dist import replicate
    from cpd_tpu.train import CheckpointManager
    manager = CheckpointManager(os.path.abspath(
        os.path.join(args.save_path, "ckpt")), track_best=False)
    start_iter = 0
    restored = manager.restore(state)
    if restored is not None:
        state = restored
        start_iter = int(restored.step)
        if rank == 0:
            print(f"=> resumed from iter {start_iter}")
    state = replicate(state, mesh)

    step = make_train_step(
        model, tx, mesh, emulate_node=args.emulate_node,
        use_aps=args.use_APS, grad_exp=args.grad_exp,
        grad_man=args.grad_man, use_kahan=args.use_kahan, mode=args.mode,
        loss_fn=(seg_loss_with_aux(255, args.aux_weight) if args.aux_head
                 else seg_cross_entropy_loss(ignore_label=255)),
        ignore_label=255, rng_keys=("dropout",))

    writer = ScalarWriter(os.path.join(args.save_path, "logs"), rank=rank,
                          tensorboard=args.tensorboard)
    progress = ProgressPrinter(args.max_iter, args.print_freq, rank=rank)
    # per-host RNG stream: hosts draw disjoint random crops
    rng = np.random.RandomState(rank)
    host_batch = global_batch // world

    # periodic evaluation — pixel accuracy + mIoU over the val split, the
    # mmseg EvalHook the reference's FCN workload relies on
    from cpd_tpu.train import make_seg_eval_step
    seg_eval = make_seg_eval_step(model, mesh,
                                  num_classes=args.num_classes)

    def validate(it: int) -> dict:
        vrng = np.random.RandomState(1234 + rank)  # fixed eval crops
        n_batches = max(1, min(8, len(val_ds) // max(global_batch, 1)))
        tot = None
        for _ in range(n_batches):
            idx = vrng.randint(0, len(val_ds), size=host_batch)
            x, y = val_ds.batch(idx, seed=-1)
            m = seg_eval(state, host_batch_to_global(x, mesh),
                         host_batch_to_global(y, mesh))
            m = {k: np.asarray(v) for k, v in m.items()}
            tot = m if tot is None else {k: tot[k] + m[k] for k in tot}
        union = tot["union"]
        present = union > 0
        miou = float(np.mean(tot["inter"][present] / union[present])) \
            if present.any() else 0.0
        out = {"loss": float(tot["loss_sum"] / max(tot["n_pix"], 1)),
               "pix_acc": float(tot["correct"] / max(tot["n_pix"], 1)),
               "miou": miou}
        if rank == 0:
            print(f"Val [{it}]: loss {out['loss']:.4f} "
                  f"pixAcc {100 * out['pix_acc']:.2f} "
                  f"mIoU {100 * out['miou']:.2f}", flush=True)
        writer.add_scalar("val/loss", out["loss"], it)
        writer.add_scalar("val/pix_acc", out["pix_acc"], it)
        writer.add_scalar("val/miou", out["miou"], it)
        return out
    last = {}
    profiler = StepProfiler(args.profile_dir, start=3)
    # SIGTERM → save at the next step boundary and exit cleanly; resume
    # continues at the saved iteration (same scheme as the other trainers)
    from cpd_tpu.train import PreemptionGuard, loss_diverged, preempt_save
    guard = PreemptionGuard()
    preempted = diverged = False
    step_no = start_iter
    t0 = now()
    def produced():
        # random-crop batch prep two steps ahead of the device
        # (utils/prefetch.py); the rng draws stay on this single
        # producer thread, so the index sequence is unchanged
        for i in range(start_iter + 1, args.max_iter + 1):
            idx = rng.randint(0, len(ds), size=host_batch)
            bx, by = ds.batch(idx, seed=i)
            yield (host_batch_to_global(bx, mesh),
                   host_batch_to_global(by, mesh))

    from cpd_tpu.utils.prefetch import Prefetcher
    batches = Prefetcher(produced(), depth=2)
    try:
        for it, (gx, gy) in enumerate(batches, start=start_iter + 1):
            if guard.should_stop():      # collective when multi-host
                preempt_save(manager, step_no, state, rank)
                preempted = True
                batches.close()
                break
            profiler.step(it)
            state, m = step(state, gx, gy)
            step_no = it
            last = {k: float(v) for k, v in m.items()}
            if loss_diverged(last["loss"], f"iter {it}", rank):
                diverged = True
                batches.close()
                break
            progress.maybe_print(it, Loss=last["loss"],
                                 PixAcc=100 * last["accuracy"])
            writer.add_scalar("train/loss", last["loss"], it)
            if it % args.val_freq == 0 or it == args.max_iter:
                last_val = validate(it)
                last.update({f"val_{k}": v for k, v in last_val.items()})
            if it % args.ckpt_freq == 0 or it == args.max_iter:
                manager.save(it, state)
    finally:
        guard.uninstall()
        batches.close()   # stop the producer even on an exception path
        # stops an in-flight jax.profiler trace even when the loop died
        # inside the window (ISSUE 11 satellite — a leaked running
        # trace poisons every later start_trace in the process)
        profiler.close()
    jax.block_until_ready(state.params)
    manager.wait()
    manager.close()
    if rank == 0 and not (preempted or diverged):
        print(f"done: {args.max_iter} iters in {now()-t0:.1f}s "
              f"final loss {last.get('loss', float('nan')):.4f}")
    writer.close()
    return {"step": step_no, "diverged": diverged, **last}


if __name__ == "__main__":
    res = main()
    sys.exit(3 if res.get("diverged") else 0)
