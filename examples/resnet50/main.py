"""ResNet-50 / ImageNet trainer — parity with `example/ResNet50/main.py`
(flags :21-55, warmup schedule :237-252, BN-without-wd param groups
:123-131, per-epoch checkpoint + auto-resume :70-75,134-138,261-269,
emulate-node sub-batch accumulation :160-202) on the shared cpd_tpu
harness.

The headline workload (SURVEY.md §6): ResNet-50, batch 32/chip, e5m2 APS
gradient all-reduce.
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
    p = argparse.ArgumentParser(
        description="cpd_tpu ImageNet Example",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    # reference surface (main.py:21-55)
    p.add_argument("--train-dir", default=None,
                   help="ImageNet root with train/ and val/ (synthetic "
                        "stand-in when absent)")
    p.add_argument("--log-dir", default="./logs")
    p.add_argument("--checkpoint-dir", default="./checkpoints",
                   help="per-epoch checkpoints + auto-resume (the "
                        "checkpoint-{epoch}.pth.tar scan of main.py:70-75)")
    # underscore aliases keep the reference's flag spellings working
    # (mix.py/main.py use --emulate_node/--use_APS/--use_kahan)
    p.add_argument("--emulate-node", "--emulate_node", type=int, default=1)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--val-batch-size", type=int, default=32)
    p.add_argument("--epochs", type=int, default=90)
    p.add_argument("--base-lr", type=float, default=0.0125,
                   help="learning rate for a single chip")
    p.add_argument("--warmup-epochs", type=float, default=5)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--wd", type=float, default=0.0001)
    p.add_argument("--use-APS", "--use_APS", action="store_true")
    p.add_argument("--use-kahan", "--use_kahan", action="store_true")
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--grad_exp", type=int, default=8)
    p.add_argument("--grad_man", type=int, default=23)
    # new surface
    p.add_argument("--arch", default="resnet50")
    p.add_argument("--init-from-torch", default="", type=str,
                   help="warm-start params+BN stats from a torchvision-"
                        "style .pth checkpoint (cpd_tpu.interop converts "
                        "the layout)")
    p.add_argument("--num-classes", default=1000, type=int)
    p.add_argument("--dist", action="store_true")
    p.add_argument("--max-batches-per-epoch", default=None, type=int)
    p.add_argument("--image-size", default=224, type=int)
    p.add_argument("--mode", default="faithful",
                   choices=["faithful", "fast", "ring"],
                   help="faithful: bit-ordered quantized reduction; "
                        "fast: quantize->psum->dequantize; ring: ordered "
                        "quantized reduce-scatter/all-gather ring with "
                        "bit-packed eXmY wire (parallel/ring.py)")
    p.add_argument("--sync-bn", action="store_true",
                   help="compute BN batch statistics across the dp axis "
                        "(per-replica stats, the reference behavior, when "
                        "off)")
    p.add_argument("--zero2", action="store_true",
                   help="ZeRO-2: momentum AND the faithful quantized "
                        "reduction sharded over dp (parallel/zero.py)")
    p.add_argument("--zero3", action="store_true",
                   help="ZeRO-3: params, momentum AND the reduction all "
                        "sharded over dp; params gathered transiently "
                        "per step (parallel/zero.py)")
    p.add_argument("--zero1", action="store_true",
                   help="ZeRO-1: shard the SGD momentum buffer 1/N over "
                        "the dp axis (parallel/zero.py)")
    p.add_argument("--grad-rounding", default="nearest",
                   choices=["nearest", "stochastic"],
                   help="rounding for every gradient-pipeline cast "
                        "(emulate-node + all-reduce — incl. the ZeRO-2/3 "
                        "sharded reduce-scatter, whose offset-indexed SR "
                        "bits match the replicated draw): stochastic = "
                        "unbiased SR (beyond-reference)")
    p.add_argument("--grad-seed", type=int, default=0,
                   help="PRNG seed for --grad-rounding stochastic")
    p.add_argument("--tensorboard", action="store_true",
                   help="also write TensorBoard event files next to the "
                        "JSONL scalars (reference mix.py:16,168-171)")
    p.add_argument("--clip-grad", default=None, type=float,
                   help="global-norm gradient clipping (applied to the "
                        "fully reduced replicated gradients, so local "
                        "norms are exact)")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace of a few steps here")
    return p


def bn_and_bias_no_wd(params):
    """wd_mask: True = apply weight decay.  BN scale/bias and all biases
    are excluded — the param-group split of main.py:123-131."""
    import jax

    def decide(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        is_bn = any("BatchNorm" in str(n) or str(n) == "batch_stats"
                    for n in names)
        is_bias = names and str(names[-1]) in ("bias", "scale")
        return not (is_bn or is_bias)

    return jax.tree_util.tree_map_with_path(decide, params)


def main(argv=None) -> dict:
    args = build_parser().parse_args(argv)

    import jax
    import jax.numpy as jnp

    from cpd_tpu.data.imagenet import load_imagenet
    from cpd_tpu.data.samplers import DistributedEpochSampler
    from cpd_tpu.models import get_model
    from cpd_tpu.parallel.dist import (dist_init, host_batch_to_global,
                                       replicate)
    from cpd_tpu.parallel.mesh import data_parallel_mesh
    from cpd_tpu.train import (CheckpointManager, PreemptionGuard,
                               create_train_state, loss_diverged,
                               make_eval_step, make_optimizer,
                               make_train_step, preempt_save,
                               warmup_step_decay)
    from cpd_tpu.utils import (ScalarWriter, StepProfiler,
                               format_validation_line)

    rank, world = dist_init() if args.dist else (0, 1)
    # after dist_init: it consults the resolved backend
    from cpd_tpu.utils import enable_compile_cache
    enable_compile_cache()
    mesh = data_parallel_mesh()
    n_dev = mesh.devices.size
    if rank == 0:
        # an unset JAX_PLATFORMS on a machine whose chip did not come up
        # resolves to cpu without a word — say what the mesh spans
        dev0 = mesh.devices.flat[0]
        print(f"=> devices: {n_dev} x {dev0.device_kind} "
              f"({dev0.platform})")

    train_ds, val_ds = load_imagenet(args.train_dir, size=args.image_size,
                                     num_classes=args.num_classes)
    global_batch = args.batch_size * n_dev * args.emulate_node
    iters_per_epoch = len(train_ds) // global_batch
    if args.max_batches_per_epoch:
        iters_per_epoch = min(iters_per_epoch, args.max_batches_per_epoch)
    if iters_per_epoch == 0:
        raise ValueError(f"dataset of {len(train_ds)} too small for global "
                         f"batch {global_batch}")

    # main.py:237-252: lr 3.2-style linear-scaled base with 5-epoch warmup
    # from 0.1x, /10 after epochs 30/60/80.  base-lr is per-chip
    # (main.py:38-39 scales by world size x emulate_node).
    scaled_lr = args.base_lr * n_dev * args.emulate_node
    schedule = warmup_step_decay(
        scaled_lr, int(args.warmup_epochs * iters_per_epoch),
        [30 * iters_per_epoch, 60 * iters_per_epoch, 80 * iters_per_epoch],
        warmup_from=scaled_lr / 10.0)

    model = get_model(args.arch, num_classes=args.num_classes,
                      dtype=jnp.bfloat16,
                      **({"bn_axis": "dp"} if args.sync_bn else {}))
    tx = make_optimizer("sgd", schedule, momentum=args.momentum,
                        weight_decay=args.wd, wd_mask=bn_and_bias_no_wd,
                        clip_norm=args.clip_grad)
    state = create_train_state(
        model, tx, jnp.zeros((2, args.image_size, args.image_size, 3)),
        jax.random.PRNGKey(args.seed))
    if args.init_from_torch:
        # Migration path: params + BN stats from a torchvision-style .pth
        # (the reference trains torchvision.models.resnet50(), main.py:67;
        # layout conversion in cpd_tpu.interop, docs/MIGRATING.md)
        from cpd_tpu.interop import (assert_compatible,
                                     import_torchvision_resnet,
                                     load_reference_checkpoint)
        converted = import_torchvision_resnet(
            load_reference_checkpoint(args.init_from_torch))
        assert_compatible(converted, {"params": state.params,
                                      "batch_stats": state.batch_stats})
        state = state.replace(params=converted["params"],
                              batch_stats=converted["batch_stats"])
        if rank == 0:
            print(f"=> imported torch checkpoint {args.init_from_torch}")
    zero = None
    if sum((args.zero1, args.zero2, args.zero3)) > 1:
        raise ValueError("--zero1/--zero2/--zero3 are mutually exclusive")
    if (args.zero2 or args.zero3) and args.mode != "faithful":
        raise ValueError("--zero2/--zero3 shard the faithful reduction; "
                         "--mode fast is not supported with them")
    if args.clip_grad is not None and (args.zero1 or args.zero2
                                       or args.zero3):
        raise ValueError("--clip-grad runs inside the optax chain, which "
                         "the ZeRO updaters bypass — unsupported together")
    if args.zero1:
        from cpd_tpu.parallel.zero import zero1_sgd
        zero = zero1_sgd(schedule, world=n_dev, momentum=args.momentum,
                         weight_decay=args.wd, wd_mask=bn_and_bias_no_wd)
        state = state.replace(opt_state=zero.init(state.params))
    elif args.zero2:
        from cpd_tpu.parallel.zero import zero2_sgd
        zero = zero2_sgd(schedule, world=n_dev, momentum=args.momentum,
                         weight_decay=args.wd, wd_mask=bn_and_bias_no_wd)
        state = state.replace(opt_state=zero.init(state.params))
    elif args.zero3:
        from cpd_tpu.parallel.zero import zero3_sgd
        zero = zero3_sgd(schedule, world=n_dev, template=state.params,
                         momentum=args.momentum, weight_decay=args.wd,
                         wd_mask=bn_and_bias_no_wd)
        # state stays in the pytree layout until after restore; checkpoints
        # are saved/restored in zero.export_state's PORTABLE layout so they
        # survive world-size changes and stay readable without --zero3

    manager = CheckpointManager(os.path.abspath(args.checkpoint_dir),
                                track_best=True)
    start_epoch = 0
    start_it = 0
    # Auto-resume must not silently overwrite an explicitly requested torch
    # import — an explicit --init-from-torch run starts from the .pth
    # ALL ZeRO stages checkpoint in the portable layout (round 5 for
    # zero1/2: pad-trimmed momentum restores at any device count)
    restored = None if args.init_from_torch else manager.restore(
        zero.portable_template(state) if zero else state)
    if restored is not None:                 # auto-resume (main.py:70-75)
        # import_state is idempotent-safe for every stage (for --zero3
        # the params are still the pytree here; make_state repacks)
        state = zero.import_state(restored) if zero else restored
        meta = manager.metadata()
        if meta is not None and "resume_it" in meta:
            # preemption checkpoint: continue the interrupted epoch at the
            # exact iteration (the epoch-seeded sampler order is
            # deterministic, so no batch is trained twice or skipped).
            # Exactness requires the SAME iteration geometry — if batch
            # size / device count / --max-batches-per-epoch changed, the
            # saved iteration indexes different samples, so restart the
            # interrupted epoch from 0 instead (re-training part of it,
            # like the reference's per-epoch resume, main.py:70-75).
            start_epoch = int(meta["epoch"])
            same_geometry = (
                int(meta.get("iters_per_epoch", -1)) == iters_per_epoch
                and int(meta.get("global_batch", -1)) == global_batch
                and int(meta.get("world", -1)) == world)
            if same_geometry:
                start_it = int(meta["resume_it"])
            elif rank == 0:
                print("=> iteration geometry changed since preemption; "
                      "restarting the interrupted epoch from iter 0")
        elif meta is not None and "epoch" in meta:
            # exact epoch from checkpoint metadata — robust to batch size /
            # device count / --max-batches-per-epoch changing between runs
            start_epoch = int(meta["epoch"]) + 1
        else:
            # no sidecar: derive from the iteration counter inside the
            # restored state itself — never from how the checkpoint file
            # happened to be numbered (mis-guessing the numbering scheme
            # resumed at the wrong epoch; round-2 review finding)
            start_epoch = int(restored.step) // max(iters_per_epoch, 1)
        if rank == 0:
            at = f" iter {start_it}" if start_it else ""
            print(f"=> auto-resumed from epoch {start_epoch}{at}")
    # orbax restores arrays committed to a single device; the train step's
    # shard_map needs the state laid out over the mesh (replicated, except
    # the ZeRO-1 momentum which is dp-sharded)
    if zero is None:
        state = replicate(state, mesh)
        extra = {}
    elif args.zero3:
        # packs params, re-pads a restored portable momentum (or zeros a
        # fresh one), and lays the whole state out dp-sharded
        state = zero.make_state(state, mesh)
        extra = {"update_fn": zero.update_fn,
                 "opt_state_spec": zero.state_spec(),
                 "params_spec": zero.param_spec(),
                 "unpack_params": zero.unpack,
                 "reduce_in_update": True}
    else:
        state, extra = zero.mesh_layout(state, mesh)

    train_step = make_train_step(
        model, tx, mesh, emulate_node=args.emulate_node,
        use_aps=args.use_APS, grad_exp=args.grad_exp,
        grad_man=args.grad_man, use_kahan=args.use_kahan, mode=args.mode,
        grad_rounding=args.grad_rounding, grad_seed=args.grad_seed,
        **extra)
    # checkpoints always persist the portable layout under any ZeRO stage
    to_ckpt = zero.export_state if zero else (lambda s: s)
    eval_step = make_eval_step(model, mesh)
    if args.zero3:
        # eval consumes the pytree layout; one jitted unflatten per
        # validation pass rebuilds it from the flat shards
        _unpack_eval = jax.jit(zero.to_pytree)
        eval_view = lambda s: s.replace(params=_unpack_eval(s.params))  # noqa: E731
    else:
        eval_view = lambda s: s                                         # noqa: E731

    writer = ScalarWriter(args.log_dir, rank=rank,
                          tensorboard=args.tensorboard)
    # Per-host epoch-seeded shuffle: each host draws its strided 1/world of
    # the epoch permutation (main.py:111-120's DistributedSampler contract).
    sampler = DistributedEpochSampler(len(train_ds), world_size=world,
                                      rank=rank)
    host_batch = global_batch // world
    val_bs = args.val_batch_size * n_dev
    val_host = val_bs // world
    result = {}
    profiler = StepProfiler(args.profile_dir, start=3)
    # SIGTERM (spot-VM preemption / maintenance) → checkpoint at the next
    # step boundary with the exact (epoch, iteration) and exit cleanly;
    # auto-resume above continues mid-epoch without re-training a batch.
    guard = PreemptionGuard()
    preempted = False
    diverged = False
    global_it = 0
    try:
        for epoch in range(start_epoch, args.epochs):
            sampler.set_epoch(epoch)
            order = np.fromiter(iter(sampler), np.int64)
            t0 = now()
            train_loss = train_acc = 0.0
            epoch_start = start_it if epoch == start_epoch else 0
            n_done = 0
            def produced(epoch=epoch, epoch_start=epoch_start, order=order):
                # host-side batch prep (the augmentation runs in the
                # native threaded executor) on a background thread, two
                # steps ahead of the device — the torch-DataLoader-worker
                # analog (main.py:111-120), same recipe as the CIFAR
                # trainer
                for i in range(epoch_start, iters_per_epoch):
                    idx = order[i * host_batch:(i + 1) * host_batch]
                    bx, by = train_ds.batch(idx, seed=epoch)
                    yield (host_batch_to_global(bx.astype(np.float32),
                                                mesh),
                           host_batch_to_global(by, mesh))

            from cpd_tpu.utils.prefetch import Prefetcher
            batches = Prefetcher(produced(), depth=2)
            for it, (gx, gy) in enumerate(batches, start=epoch_start):
                if guard.should_stop():      # collective when multi-host
                    preempt_save(
                        manager, state.step, to_ckpt(state), rank,
                        what="step",
                        metadata={"epoch": epoch, "resume_it": it,
                                  "iters_per_epoch": iters_per_epoch,
                                  "global_batch": global_batch,
                                  "world": world})
                    if rank == 0:
                        print(f"   (epoch {epoch} iter {it})")
                    preempted = True
                    batches.close()
                    break
                global_it += 1
                profiler.step(global_it)
                state, m = train_step(state, gx, gy)
                step_loss = float(m["loss"])
                if loss_diverged(step_loss, f"epoch {epoch} iter {it}",
                                 rank, hint="try --use-APS / more "
                                            "mantissa bits"):
                    diverged = True
                    batches.close()
                    break
                train_loss += step_loss
                train_acc += float(m["accuracy"])
                n_done += 1
            if preempted or diverged:
                break
            jax.block_until_ready(state.params)
            dt = now() - t0
            n_done = max(n_done, 1)
            imgs_per_sec = n_done * global_batch / dt

            # validate (main.py:215-235)
            val_loss = val_top1 = val_top5 = 0.0
            k = 0
            n_val = (len(val_ds) // val_bs) * val_bs
            eval_state = eval_view(state)
            for lo in range(0, n_val, val_bs):
                sel = np.arange(lo + rank * val_host, lo + (rank + 1) * val_host)
                x, y = val_ds.batch(sel)
                m = eval_step(eval_state,
                              host_batch_to_global(x.astype(np.float32), mesh),
                              host_batch_to_global(y, mesh))
                val_loss += float(m["loss"])
                val_top1 += float(m["top1"])
                val_top5 += float(m["top5"])
                k += 1
            k = max(k, 1)
            result = {
                "epoch": epoch, "train_loss": train_loss / n_done,
                "train_acc": train_acc / n_done,
                "val_loss": val_loss / k, "val_top1": val_top1 / k,
                "val_top5": val_top5 / k, "img_per_sec": imgs_per_sec,
            }
            if rank == 0:
                print(f"Epoch {epoch}: loss {result['train_loss']:.4f} "
                      f"acc {100*result['train_acc']:.2f} "
                      f"({imgs_per_sec:.1f} img/s)")
                print(format_validation_line(result["val_loss"],
                                             100 * result["val_top1"],
                                             100 * result["val_top5"]))
            writer.add_scalar("train/loss", result["train_loss"], epoch)
            writer.add_scalar("val/top1", result["val_top1"], epoch)
            # per-epoch checkpoint keyed by the TRUE global step: monotonic no
            # matter how earlier checkpoints in the directory were numbered, so
            # a resumed run can never be shadowed by a stale higher-numbered
            # file.  The reference's epoch-named files (checkpoint-{epoch}
            # .pth.tar, main.py:261-269) are matched in behavior — one
            # checkpoint per epoch, auto-resume — with the epoch recorded in
            # sidecar metadata instead of the filename.
            manager.save(int(state.step), to_ckpt(state),
                         best_metric=100 * result["val_top1"],
                         metadata={"epoch": epoch,
                                   "iters_per_epoch": iters_per_epoch})
    finally:
        guard.uninstall()
        if "batches" in locals():
            batches.close()   # stop the producer on any exception path
        # stops an in-flight jax.profiler trace even when the loop died
        # inside the window (ISSUE 11 satellite — a leaked running
        # trace poisons every later start_trace in the process)
        profiler.close()
    manager.wait()
    manager.close()
    writer.close()
    result["diverged"] = diverged
    return result


if __name__ == "__main__":
    res = main()
    sys.exit(3 if res.get("diverged") else 0)
