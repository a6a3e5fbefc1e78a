"""Worker for the two-process distributed test (test_multiprocess.py).

Run as `python tests/mp_worker.py <rank> <port> <outdir>`.  Each of the two
processes owns ONE local CPU device; jax's coordination service stitches
them into a 2-device global mesh — the CPU stand-in for the reference's
one-process-per-GPU NCCL world (dist_util.py:96-131).

Exercises the three multi-process paths that single-process tests cannot
reach (VERDICT r2, Missing #4):
  * `dist_init` with an explicit coordinator (parallel/dist.py:76-84),
  * `host_batch_to_global`'s make_array_from_process_local_data branch
    (parallel/dist.py:121),
  * the faithful quantized `sum_gradients` collective across processes.

Rank 0 writes the reduced tree to <outdir>/result.npz; the parent test
asserts bit-equality with the single-process 2-device run of the same
reduction.
"""

import os
import sys


def main() -> None:
    rank, port, outdir = int(sys.argv[1]), sys.argv[2], sys.argv[3]

    import jax

    # a CPU-tier test worker: pin the platform in this process too, so it
    # stays off any chip whatever environment the parent passed down
    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from cpd_tpu.parallel import make_mesh, make_sum_gradients_fn
    from cpd_tpu.parallel.dist import dist_init, host_batch_to_global

    got_rank, world = dist_init(coordinator_address=f"localhost:{port}",
                                num_processes=2, process_id=rank)
    assert got_rank == rank, (got_rank, rank)
    assert world == 2, world
    assert len(jax.devices()) == 2, jax.devices()
    assert len(jax.local_devices()) == 1, jax.local_devices()

    mesh = make_mesh(dp=2)

    # Same data as the parent's single-process arm: each process holds its
    # contiguous per-rank block (train_util.py:212-215 host-order convention)
    rng = np.random.RandomState(7)
    full = {"w": rng.randn(2, 9, 4).astype(np.float32),
            "b": rng.randn(2, 7).astype(np.float32)}
    global_tree = jax.tree.map(
        lambda a: host_batch_to_global(a[rank:rank + 1], mesh, "dp"), full)
    for leaf in jax.tree.leaves(global_tree):
        assert leaf.shape[0] == 2, leaf.shape  # global, not local, batch

    reduce_fn = make_sum_gradients_fn(mesh, axis_name="dp", use_aps=True,
                                      grad_exp=5, grad_man=2, use_kahan=True)
    got = jax.tree.map(np.asarray, reduce_fn(global_tree))

    # ---- full train step across the process boundary: BN batch stats,
    # APS pmax, the quantized Kahan collective, and the SGD update all
    # run over the 2-device cross-process mesh (the per-rank shape of
    # the reference's DDP step, main.py:111-169) ----
    step_result = _train_step_phase(mesh, rank * 2, (rank + 1) * 2)

    # ---- pipeline across the process boundary (round 5): each process
    # IS one pipeline stage — microbatch activations ppermute over the
    # process link, and the vocab-sharded embed/head's lookup psum,
    # head broadcast, and vocab-parallel CE all cross it too ----
    pp_mesh = make_mesh(dp=1, pp=2)
    pp_result = _pp_phase(pp_mesh)

    if rank == 0:
        tmp = os.path.join(outdir, "tmp_result.npz")  # savez appends .npz
        np.savez(tmp, **got, **step_result, **pp_result)
        os.replace(tmp, os.path.join(outdir, "result.npz"))
    print(f"mp_worker rank={rank} ok", flush=True)


def _train_step_phase(mesh, lo: int, hi: int) -> dict:
    """One quantized train step; this process feeds batch rows [lo, hi)
    (the whole batch single-process, a half per rank two-process).
    Returns flattened post-step params, BN batch_stats, and loss — all
    replicated outputs, so every rank can read them.  Shared by the
    worker and the parent test's single-process arm so the two
    configurations cannot drift."""
    import jax
    import numpy as np

    from cpd_tpu.parallel.dist import host_batch_to_global
    from cpd_tpu.train import (create_train_state, make_optimizer,
                               make_train_step)
    from cpd_tpu.models import tiny_cnn

    rng = np.random.RandomState(11)
    x = rng.randn(4, 8, 8, 3).astype(np.float32)
    y = rng.randint(0, 10, 4).astype(np.int32)

    model = tiny_cnn(width=4)
    tx = make_optimizer("sgd", lambda s: 0.1, momentum=0.9)
    state = create_train_state(model, tx, x[:1], jax.random.PRNGKey(3))
    step = make_train_step(model, tx, mesh, use_aps=True, grad_exp=5,
                           grad_man=2, use_kahan=True, donate=False)
    xg = host_batch_to_global(x[lo:hi], mesh, "dp")
    yg = host_batch_to_global(y[lo:hi], mesh, "dp")
    state, metrics = step(state, xg, yg)

    out = {"step_loss": np.asarray(metrics["loss"])}
    for col, tree in (("param", state.params),
                      ("bnstat", state.batch_stats)):
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            out[col + jax.tree_util.keystr(path)] = np.asarray(leaf)

    # ---- stochastic-rounding step across the same boundary: the SR key
    # schedule (grad_sr_key + in-program rank folds, never host identity)
    # must make process boundaries invisible too — MULTIHOST.md's
    # "multi-host-safe by construction" claim, executed ----
    sr_state = create_train_state(model, tx, x[:1], jax.random.PRNGKey(3))
    sr_step = make_train_step(model, tx, mesh, use_aps=True, grad_exp=4,
                              grad_man=3, grad_rounding="stochastic",
                              grad_seed=5, donate=False)
    sr_state, sr_metrics = sr_step(sr_state, xg, yg)
    out["sr_step_loss"] = np.asarray(sr_metrics["loss"])
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            sr_state.params)[0]:
        out["srparam" + jax.tree_util.keystr(path)] = np.asarray(leaf)
    return out


def _pp_phase(mesh) -> dict:
    """One vocab-sharded (vocab_pp) pipelined-LM train step on a pp=2
    mesh — shared by the worker (stages in different PROCESSES) and the
    parent's single-process arm, so the two configurations cannot
    drift.  Returns the replicated loss and a replicated all-gather of
    the post-step params."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cpd_tpu.models import pipelined_lm
    from cpd_tpu.train import make_optimizer
    from cpd_tpu.train.pp import make_pp_train_step, pp_state_specs
    from cpd_tpu.train.state import TrainState

    kw = dict(vocab_size=32, d_model=16, n_layers=2, n_heads=2, d_ff=32)
    model = pipelined_lm(**kw, pp_axis="pp", pp_size=2, vocab_pp=True)
    rng = np.random.RandomState(13)
    toks = rng.randint(0, 32, (4, 8)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1)
    # init is mesh-independent (full global stack regardless of pp/vocab
    # settings, pipeline_lm.init)
    variables = model.init(jax.random.PRNGKey(5), jnp.asarray(toks[:1]))
    tx = make_optimizer("sgd", lambda s: jnp.float32(0.1), momentum=0.9)
    state = TrainState(step=jnp.zeros([], jnp.int32),
                       params=variables["params"], batch_stats={},
                       opt_state=tx.init(variables["params"]))
    specs = pp_state_specs(state, vocab_pp=True)

    def put(spec, leaf):
        # every process holds the full host value; each contributes its
        # addressable shards — works one- AND two-process
        if not isinstance(leaf, jnp.ndarray) and not np.isscalar(
                leaf) and not isinstance(leaf, np.ndarray):
            return leaf                      # e.g. the empty batch_stats
        sh = NamedSharding(mesh, spec)
        arr = np.asarray(leaf)
        return jax.make_array_from_callback(arr.shape, sh,
                                            lambda idx: arr[idx])

    # specs as the PRIMARY tree: PartitionSpec leaves pair with the
    # state's arrays (and with the empty batch_stats dict, passed back)
    sharded = jax.tree.map(put, specs, state,
                           is_leaf=lambda x: isinstance(x, P))
    step = make_pp_train_step(model, tx, mesh, n_microbatches=2,
                              use_aps=True, grad_exp=5, grad_man=2,
                              donate=False)
    new_state, metrics = step(sharded, jnp.asarray(toks),
                              jnp.asarray(tgts))
    gather = jax.jit(lambda p: p,
                     out_shardings=NamedSharding(mesh, P()))
    full = jax.tree.map(np.asarray, gather(new_state.params))
    out = {"pp_loss": np.asarray(metrics["loss"])}
    for path, leaf in jax.tree_util.tree_flatten_with_path(full)[0]:
        out["ppparam" + jax.tree_util.keystr(path)] = leaf
    return out


if __name__ == "__main__":
    main()
