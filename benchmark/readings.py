"""The readings a cell's limits are set from (README.md, "What `correct`
means"): `check.first_steps` of one cell on many seeds in one process, as
stated or with an arm or a fault planted, no measured window.

    python benchmark/readings.py --workload CELL --seeds 1,2,3 \
        [--reduce '{"grad_man": 1}'] [--program-lr-scale 2] \
        [--fault half_batch] [--config '{...}'] [--traffic '{...}'] \
        [--out FILE]

`--reduce` overlays the traffic's `reduce` keywords (a control: the next
format down, APS off), `--config` and `--traffic` the top-level keys of
the two files (a witness: the program in float32 compute at a batch that
fits); `--program-lr-scale` scales the learning rate in
the program's optimizer only; `--fault half_batch` feeds the program the
first half of every batch twice (half of the batch left out, the mean
taken over the rest) and leaves the reference the whole batch, `--fault
unchanged` makes the step return its state as it was, `--fault
without_exchange` leaves every replica parameters of its own.  One JSON
line a seed on stdout, each naming the device it ran on, and a last line
with every reading's least and most.  The numbers are distances, not
times; the limits that stand in the traffic files were read on the chip.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def half_batch(step, mesh=None):
    """`step` fed the first half of each batch twice."""
    import jax
    import jax.numpy as jnp

    def twice(x):
        half = x[:x.shape[0] // 2]
        return jnp.concatenate([half, half])

    twice_both = jax.jit(lambda a, b: (twice(a), twice(b)))

    def faulty(state, a, b):
        return step(state, *twice_both(a, b))

    return faulty


def unchanged(step, mesh=None):
    """`step` returning its state as it was, with the true metrics."""
    from benchmark.check import copy_tree

    def faulty(state, a, b):
        kept = copy_tree(state)
        _, metrics = step(state, a, b)
        return kept, metrics

    return faulty


def without_exchange(step, mesh):
    """`step` after which every replica holds parameters of its own, as
    it would had it stepped on its own gradient."""
    import dataclasses

    import jax
    from jax.sharding import PartitionSpec as P

    def drift(params):
        rank = jax.lax.axis_index("dp").astype("float32")
        return jax.tree.map(lambda w: w * (1.0 + 1e-3 * rank), params)

    apart = jax.jit(jax.shard_map(drift, mesh=mesh, in_specs=(P(),),
                                  out_specs=P(), check_vma=False))

    def faulty(state, a, b):
        state, metrics = step(state, a, b)
        return dataclasses.replace(state, params=apart(state.params)), metrics

    return faulty


# the faults a training cell can have, each planted under the timed path:
# `(step, mesh) -> step`.  The tests plant the same ones
FAULTS = {"half_batch": half_batch, "unchanged": unchanged,
          "without_exchange": without_exchange}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--reduce", default="{}")
    p.add_argument("--config", default="{}")
    p.add_argument("--traffic", default="{}")
    p.add_argument("--program-lr-scale", type=float, default=1.0)
    p.add_argument("--fault", choices=sorted(FAULTS))
    p.add_argument("--out", help="also append the lines to this file")
    args = p.parse_args(argv)

    import dataclasses

    import jax

    from benchmark import check, run
    from cpd_tpu.utils import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    found = copy.deepcopy(run.discover()[args.workload])
    found["config"].update(json.loads(args.config))
    found["traffic"].update(json.loads(args.traffic))
    stated = copy.deepcopy(found["config"])    # what the reference steps by
    found["traffic"]["reduce"].update(json.loads(args.reduce))
    for key in ("lr", "lr_per_256_items"):
        if key in stated["optimizer"]:
            found["config"]["optimizer"][key] = (
                stated["optimizer"][key] * args.program_lr_scale)
    devices = jax.devices()
    built = run.build(found, devices)
    runner = built["runner"]
    if args.fault:
        runner = dataclasses.replace(
            runner, step=FAULTS[args.fault](runner.step, built["mesh"]))
    counter = run.CompileCounter().install()
    arm = {"workload": args.workload, "reduce": found["traffic"]["reduce"],
           "config": json.loads(args.config),
           "traffic": json.loads(args.traffic),
           "program_lr_scale": args.program_lr_scale, "fault": args.fault,
           "platform": devices[0].platform, "kind": devices[0].device_kind}

    def emit(record):
        text = json.dumps(run.plain(record))
        print(text, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(text + "\n")

    seen = {}
    for seed in map(int, args.seeds.split(",")):
        k_weights, k_data = jax.random.split(run.seed_key(seed))
        batches = [built["make_batch"](jax.random.fold_in(k_data, i))
                   for i in range(check.STEPS)]
        readings, facts = check.first_steps(
            runner, stated, built["init"], k_weights, batches, counter)
        for name, value in readings.items():
            seen.setdefault(name, []).append(value)
        emit({**arm, "seed": seed, **readings,
              **{k: facts[k] for k in (
                  "update_rel_err_by_part", "grad_norm_gap_leaf",
                  "change_norm_gap_leaf", "step_losses",
                  "reference_step_losses", "check_s")},
              "repeats": facts["first_loss_again"] == facts["step_losses"][0]})
    emit({**arm, "seeds": len(next(iter(seen.values()))),
          "least": {k: min(v) for k, v in seen.items()},
          "most": {k: max(v) for k, v in seen.items()}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
