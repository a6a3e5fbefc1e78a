"""DavidNet / CIFAR-10 DAWNBench trainer — parity with
`example/DavidNet/dawn.py` (flags :11-26, schedule+opt :65-79, epoch loop
via train_utils/utils train() :391-436), on the shared cpd_tpu harness.

Reference semantics kept: PiecewiseLinear LR 0 -> 0.4*lr_scale at epoch 5
-> 0 at epoch `--epoch` (dawn.py:65), nesterov SGD with weight decay
5e-4 * batch_size (dawn.py:73-79), crop/flip/cutout-8 augmentation
(dawn.py:66), `--half` as bf16 compute (TPU's half precision — the MXU
dtype), `--loss_scale` multiplied into the loss and never unscaled
(utils.py:332-334), TSV/Table loggers (dawn.py:37-47, utils.py:44-56).

`--arch davidnet_graph` trains the dict-graph-defined form of the network
(models/davidnet_graph.py — the reference's TorchGraph definition style,
utils.py:258-292); forward-parity with `--arch davidnet` is pinned by
tests/test_graph.py.
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


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="cpd_tpu DavidNet DAWNBench")
    # reference surface (dawn.py:11-26)
    p.add_argument("--dist", default=0, type=int)
    p.add_argument("--epoch", default=24, type=int)
    p.add_argument("--warm_up_epoch", default=5, type=int)
    p.add_argument("-b", "--batch_size", default=512, type=int)
    p.add_argument("--momentum", default=0.9, type=float)
    p.add_argument("--workers", default=4,
                   help="accepted for reference CLI parity (dawn.py:15, "
                        "DataLoader workers); unused here — batches are "
                        "built by the vectorized pipeline + native "
                        "executor, no worker pool")
    p.add_argument("--half", default=0, type=int)
    p.add_argument("--lr_scale", default=1.0, type=float)
    p.add_argument("--seed", default=0, type=int)
    p.add_argument("--grad_exp", default=8, type=int)
    p.add_argument("--grad_man", default=23, type=int)
    p.add_argument("--use_APS", action="store_true")
    p.add_argument("--use_kahan", action="store_true")
    p.add_argument("--loss_scale", default="1",
                   help="static scale int (reference dawn.py:24, never "
                        "unscaled) or 'dynamic' for GradScaler-style "
                        "scaling (train/scaling.py; beyond-reference)")
    # new surface
    p.add_argument("--arch", default="davidnet")
    p.add_argument("--data-root", default=None)
    p.add_argument("--max-batches-per-epoch", default=None, type=int,
                   help="truncate epochs (smoke tests)")
    p.add_argument("--emulate_node", default=1, type=int)
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace of a few steps here")
    p.add_argument("--mode", default="faithful",
                   choices=["faithful", "fast"])
    return p


def main(argv=None) -> dict:
    args = build_parser().parse_args(argv)

    import jax
    import jax.numpy as jnp

    from cpd_tpu.data import CIFAR10Pipeline, load_cifar10
    from cpd_tpu.models import get_model
    from cpd_tpu.parallel.dist import dist_init, host_batch_to_global
    from cpd_tpu.parallel.mesh import data_parallel_mesh
    from cpd_tpu.train import (Timer, create_train_state,
                               loss_diverged, make_eval_step,
                               make_optimizer, make_train_step,
                               piecewise_linear)
    from cpd_tpu.utils import StepProfiler, TableLogger, TSVLogger

    rank, world = dist_init() if args.dist else (0, 1)
    # after dist_init: it consults the resolved backend
    from cpd_tpu.utils import enable_compile_cache
    enable_compile_cache()
    mesh = data_parallel_mesh()
    n_dev = mesh.devices.size

    train_x, train_y, test_x, test_y = load_cifar10(args.data_root)
    dataset_len = len(train_y)
    global_batch = args.batch_size * n_dev * args.emulate_node
    iters_per_epoch = dataset_len // global_batch
    if args.max_batches_per_epoch:
        iters_per_epoch = min(iters_per_epoch, args.max_batches_per_epoch)

    # dawn.py:65 knots are epochs; the step-based schedule scales them.
    schedule = piecewise_linear(
        [0, args.warm_up_epoch * iters_per_epoch,
         args.epoch * iters_per_epoch],
        [0.0, 0.4 * args.lr_scale, 0.0])
    # dawn.py:73-79: nesterov SGD, wd = 5e-4 * batch_size
    tx = make_optimizer("nesterov", schedule, momentum=args.momentum,
                        weight_decay=5e-4 * args.batch_size)
    dynamic_scale = str(args.loss_scale).strip().lower() == "dynamic"
    if dynamic_scale:
        from cpd_tpu.train.scaling import with_dynamic_loss_scale
        tx = with_dynamic_loss_scale(tx)
    loss_scale = "dynamic" if dynamic_scale else float(args.loss_scale)

    dtype = jnp.bfloat16 if args.half else jnp.float32
    model = get_model(args.arch, dtype=dtype)
    state = create_train_state(model, tx, jnp.zeros((2, 32, 32, 3)),
                               jax.random.PRNGKey(args.seed))

    train_step = make_train_step(
        model, tx, mesh, emulate_node=args.emulate_node,
        use_aps=args.use_APS, grad_exp=args.grad_exp,
        grad_man=args.grad_man, use_kahan=args.use_kahan,
        loss_scale=loss_scale, mode=args.mode)
    eval_step = make_eval_step(model, mesh)

    host_batch = global_batch // world
    pipeline = CIFAR10Pipeline(train_x, train_y, host_batch, augment=True,
                               cutout=8)
    eval_bs = max(n_dev, (min(1000, len(test_y)) // n_dev) * n_dev)
    eval_host = eval_bs // world
    eval_pipe = CIFAR10Pipeline(test_x, test_y, eval_bs, augment=False)

    table = TableLogger(rank=rank)
    tsv = TSVLogger()
    timer = Timer()
    profiler = StepProfiler(args.profile_dir, start=3)
    global_step = 0
    result = {}
    diverged = False
    try:
        for epoch in range(1, args.epoch + 1):
            rng = np.random.RandomState(args.seed + epoch)
            # same epoch permutation on every host; each takes its contiguous
            # 1/world block of every global batch
            order = rng.permutation(dataset_len)[:iters_per_epoch * global_batch]
            train_loss = train_acc = 0.0
            n = 0
            def produced(order=order, epoch=epoch):
                # batch prep (native threaded augmentation + device transfer)
                # two steps ahead of the device (utils/prefetch.py) — matters
                # most here: DAWNBench is a wall-clock speed run
                for lo in range(0, len(order), global_batch):
                    sel = order[lo + rank * host_batch:
                                lo + (rank + 1) * host_batch]
                    bx, by = pipeline.batch(sel, seed=epoch)
                    yield (host_batch_to_global(bx, mesh),
                           host_batch_to_global(by, mesh))

            from cpd_tpu.utils.prefetch import Prefetcher
            batches = Prefetcher(produced(), depth=2)
            try:
                for gx, gy in batches:
                    global_step += 1
                    profiler.step(global_step)
                    state, m = train_step(state, gx, gy)
                    step_loss = float(m["loss"])
                    if loss_diverged(step_loss, f"step {global_step}", rank,
                                     hint="lower --loss_scale / try "
                                          "--use_APS"):
                        diverged = True
                        break
                    train_loss += step_loss
                    train_acc += float(m["accuracy"])
                    n += 1
            finally:
                batches.close()   # stop the producer on any exit path
            if diverged:
                break
            jax.block_until_ready(state.params)
            train_time = timer()                 # counts toward total

            test_loss = test_acc = 0.0
            k = 0
            limit = (len(test_y) // eval_bs) * eval_bs
            for lo in range(0, limit, eval_bs):
                sel = np.arange(lo + rank * eval_host,
                                lo + (rank + 1) * eval_host)
                x, y = eval_pipe.batch(sel)
                m = eval_step(state, host_batch_to_global(x, mesh),
                              host_batch_to_global(y, mesh))
                test_loss += float(m["loss"])
                test_acc += float(m["top1"])
                k += 1
            # test time excluded from DAWNBench total (dawn.py's
            # test_time_in_total=False).
            test_time = timer(include_in_total=False)
            total = timer.total_time

            result = {
                "epoch": epoch,
                "lr": float(schedule(epoch * iters_per_epoch)),
                "train time": train_time, "train loss": train_loss / max(n, 1),
                "train acc": train_acc / max(n, 1),
                "test time": test_time, "test loss": test_loss / max(k, 1),
                "test acc": test_acc / max(k, 1),
                "total time": total,
            }
            table.append(result)
            tsv.append(result)
    finally:
        # stops an in-flight jax.profiler trace even when the
        # loop died inside the window (ISSUE 11 satellite -- a
        # leaked running trace poisons every later start_trace
        # in the process)
        profiler.close()
    if rank == 0:
        print(tsv)
    result["diverged"] = diverged
    return result


if __name__ == "__main__":
    res = main()
    sys.exit(3 if res.get("diverged") else 0)
