"""Gradient-reduction transport microbenchmark: gather vs ring vs psum.

The hot path at scale is the gradient all-reduce (MLPerf TPU-pod scaling;
ISSUE 3), and the interesting axis is the TRANSPORT: the faithful gather
path ships (W-1)·n fp32 elements per device, the ring transport
(parallel/ring.py) ships ~2·(W-1)·n/W bit-packed eXmY code words.  This
tool times `sum_gradients` in each mode on the current backend and reports
the ANALYTIC per-device bytes-on-wire alongside (on the CPU mesh there is
no real wire — the byte counters are the load-bearing numbers there; on
TPU the timing is real too).

    python tools/bench_reduce.py                  # measure, JSON line out
    python tools/bench_reduce.py --smoke          # CI gate: tiny sizes,
        asserts ring==oracle bitwise parity (per-tensor AND block-scaled,
        the fused-digest == wire_digest parity incl. a wire_flip drill),
        the byte-counter invariants (ring >= 2x fewer wire bytes than
        the faithful gather at W=8 for e5m2), the e4m3-blocked-vs-e5m7
        frontier point, and the verified-ring cost bounds; exit 1 on
        any violation
    python tools/bench_reduce.py --block-sweep    # ISSUE 9 frontier:
        per-tensor APS vs block-scaled accuracy (vs the exact fp32 ring
        oracle) against analytic wire bytes incl. the scale sidecar

Prints ONE JSON line; `bench.py` reports the same analytic byte accounting
as its `reduction` block.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _ensure_multidevice():
    """Standalone runs on CPU get the 8-virtual-device platform (the same
    trick as tests/conftest.py) — must happen before jax imports."""
    if "--help" in sys.argv or "-h" in sys.argv:
        return
    plat = os.environ.get("JAX_PLATFORMS", "")
    if plat in ("", "cpu") and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_"
                                     "count=8").strip()
    # a CI gate that defaults to the CPU mesh says so on its first line
    print(f"# {os.path.basename(__file__)}: JAX_PLATFORMS="
          f"{os.environ.get('JAX_PLATFORMS') or '(unset: jax picks)'}",
          flush=True)


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# the ONE wall-clock helper (ISSUE 11 satellite: this tool's ad-hoc
# perf_counter pairs deduped onto cpd_tpu.obs.timing)
from cpd_tpu.obs.timing import now  # noqa: E402


def measure(n: int, exp: int, man: int, iters: int, use_kahan: bool,
            rounding: str, bucket_elems=None, block_scale: bool = False,
            block_size: int = 128) -> dict:
    """Time sum_gradients in each transport mode on the current backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cpd_tpu.parallel import make_sum_gradients_fn
    from cpd_tpu.parallel.mesh import data_parallel_mesh
    from cpd_tpu.parallel.ring import transport_table

    mesh = data_parallel_mesh()
    world = len(jax.devices())
    rng = np.random.RandomState(0)
    stacked = {"g": (rng.randn(world, n) * 0.1).astype(np.float32)}
    sharded = jax.tree.map(
        lambda g: jax.device_put(jnp.asarray(g),
                                 NamedSharding(mesh, P("dp"))), stacked)
    key = jax.random.PRNGKey(0) if rounding == "stochastic" else None

    out = {"world": world, "elements": n, "format": [exp, man],
           "use_kahan": use_kahan, "rounding": rounding,
           "bucket_elems": bucket_elems,
           "block_scale": block_scale,
           "block_size": block_size if block_scale else None,
           "platform": jax.devices()[0].platform,
           "bytes_on_wire_per_device": transport_table(
               n, world, exp, man, use_kahan=use_kahan,
               block_size=block_size if block_scale else None),
           "modes": {}}
    ring_kw = (dict(block_scale=True, block_size=block_size)
               if block_scale else {})
    for mode in ("faithful", "ring", "fast"):
        fn = make_sum_gradients_fn(mesh, axis_name="dp", grad_exp=exp,
                                   grad_man=man, use_kahan=use_kahan,
                                   mode=mode, rounding=rounding, key=key,
                                   bucket_elems=bucket_elems,
                                   **(ring_kw if mode == "ring" else {}))
        r = fn(sharded)
        np.asarray(r["g"])  # compile + sync
        best = float("inf")
        for _ in range(max(1, iters)):
            t0 = now()
            r = fn(sharded)
            np.asarray(r["g"])
            best = min(best, now() - t0)
        out["modes"][mode] = {"best_ms": round(best * 1e3, 3),
                              "elems_per_sec": round(n / best, 1)}

    # verified ring (ISSUE 4/9): same transport + the integrity layer.
    # Two arms per (clean, verified) pair: the XLA hop composition and
    # the fused single-kernel wire path (ops/quantize.hop_pack_pallas —
    # interpret-mode on non-TPU backends, so its ABSOLUTE time off-TPU
    # is the kernel interpreter's, not the transport's; the
    # verified/clean RATIO within each arm is the load-bearing number,
    # and docs/PERF.md quotes exactly that).
    from cpd_tpu.compat import shard_map
    from cpd_tpu.parallel.ring import ring_quantized_sum
    on_tpu = jax.devices()[0].platform == "tpu"

    def time_ring(verify, fused):
        def body(st, k=key):
            out = ring_quantized_sum(st["g"][0], "dp", exp, man,
                                     use_kahan=use_kahan, key=k,
                                     verify=verify, fused=fused,
                                     interpret=fused and not on_tpu,
                                     **ring_kw)
            if verify:
                vec, rep = out
                return vec, rep["ok"]
            return out, jnp.ones([], jnp.int32)
        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                               out_specs=(P(), P()), check_vma=False))
        vec, ok = fn(sharded)
        np.asarray(vec)
        best = float("inf")
        for _ in range(max(1, iters)):
            t0 = now()
            vec, ok = fn(sharded)
            np.asarray(vec)
            best = min(best, now() - t0)
        return best * 1e3, int(ok)

    ring_ms = out["modes"]["ring"]["best_ms"]
    ver_ms, ok = time_ring(True, False)
    out["modes"]["ring_verified"] = {
        "best_ms": round(ver_ms, 3),
        "elems_per_sec": round(n / (ver_ms / 1e3), 1),
        "ok": ok,
        "overhead_vs_ring_pct": (round(100.0 * (ver_ms - ring_ms)
                                       / ring_ms, 1) if ring_ms else None),
    }
    # the fused wire pair is only defined where the kernel is: packed
    # plain hops (and blocked hops at kernel-aligned block sizes)
    fusable = (not use_kahan and man >= 2 and not (exp == 8 and man == 23)
               and (not block_scale or (block_size % 128 == 0
                                        and 65536 % block_size == 0)))
    if fusable:
        clean_f, _ = time_ring(False, True)
        ver_f, ok_f = time_ring(True, True)
        out["modes"]["ring_fused"] = {
            "best_ms": round(clean_f, 3), "interpret": not on_tpu}
        out["modes"]["ring_fused_verified"] = {
            "best_ms": round(ver_f, 3), "ok": ok_f,
            "interpret": not on_tpu,
            "overhead_vs_ring_fused_pct": round(
                100.0 * (ver_f - clean_f) / clean_f, 1),
        }
    return out


def bucket_sweep(n: int, exp: int, man: int, iters: int,
                 sizes: list) -> dict:
    """Time the bucketed faithful gather and the bucketed ring at each
    bucket size (None = one whole-tree bucket/ring) — the ISSUE 8
    satellite: `bucket_elems` is a measured knob, not a guess.  The
    pytree is split into 64 equal leaves so the layout actually varies
    with the cap."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cpd_tpu.parallel import make_sum_gradients_fn
    from cpd_tpu.parallel.mesh import data_parallel_mesh

    mesh = data_parallel_mesh()
    world = len(jax.devices())
    rng = np.random.RandomState(0)
    n_leaves = 64
    per = max(n // n_leaves, 1)
    stacked = {f"g{i:02d}": (rng.randn(world, per) * 0.1)
               .astype(np.float32) for i in range(n_leaves)}
    sharded = jax.tree.map(
        lambda g: jax.device_put(jnp.asarray(g),
                                 NamedSharding(mesh, P("dp"))), stacked)

    def time_one(mode, be):
        kw = dict(bucket_elems=be)
        if mode == "faithful":
            kw["bucket"] = True if be is None else None
        fn = make_sum_gradients_fn(mesh, axis_name="dp", grad_exp=exp,
                                   grad_man=man, mode=mode, **kw)
        r = fn(sharded)
        np.asarray(r["g00"])
        best = float("inf")
        for _ in range(max(1, iters)):
            t0 = now()
            r = fn(sharded)
            np.asarray(r["g00"])
            best = min(best, now() - t0)
        return round(best * 1e3, 3)

    rows = []
    for be in sizes:
        rows.append({"bucket_elems": be,
                     "faithful_ms": time_one("faithful", be),
                     "ring_ms": time_one("ring", be)})
    return {"world": world, "elements": per * n_leaves,
            "leaves": n_leaves, "format": [exp, man],
            "platform": jax.devices()[0].platform, "rows": rows}


def _frontier_probe(world: int, n: int, region: int = 32,
                    spread: int = 40, seed: int = 3):
    """Block-structured gradient probe for the accuracy sweep: magnitudes
    are drawn per `region`-element run from a log-uniform envelope
    spanning ±`spread` octaves — the layer-to-layer (and channel-to-
    channel) dynamic-range spread real gradient trees show, which is
    exactly the structure per-TENSOR scaling wastes format range on and
    per-BLOCK scaling recovers (EQuARX, PAPERS.md #2).  The default
    ±40 octaves overflows a per-tensor e5's ~40-octave window (values
    at the far end flush/saturate around the single shared shift) while
    any per-block shift still lands its own block at the format top —
    the regime the EQuARX frontier claim is about."""
    import numpy as np
    rng = np.random.RandomState(seed)
    n_regions = -(-n // region)
    # ONE scale per region, shared across ranks: a layer's gradient
    # scale is a property of the layer, identical on every data-
    # parallel rank — independent per-rank scales would let each
    # region's SUM ride its luckiest rank and hide the flush
    scale = np.exp2(rng.uniform(-spread, spread,
                                (1, n_regions))).repeat(region, axis=1)
    return (rng.randn(world, n) * scale[:, :n]).astype(np.float32)


def block_frontier_sweep(n: int, formats=((4, 3), (5, 2), (5, 7)),
                         blocks=(16, 32, 64, 128, 256),
                         world: int = 8) -> dict:
    """The accuracy-vs-wire-bytes frontier (ISSUE 9 satellite): for each
    eXmY format, the per-tensor APS ring vs the block-scaled ring at
    each block size, scored against the exact fp32 ring oracle on the
    block-structured probe above.

    Accuracy rides the single-device `ring_oracle_sum` — bit-equal to
    the distributed transport by the oracle-parity gates, so no mesh is
    needed and the sweep is pure math.  Bytes are the analytic per-
    device ring wire (`ring_transport_bytes`, sidecar lane included).
    The headline row pair docs/PERF.md quotes: e4m3 block-scaled at
    fewer wire bytes than per-tensor e5m7, at equal or better error."""
    import jax.numpy as jnp
    import numpy as np

    from cpd_tpu.parallel.aps import (aps_max_exponents,
                                      aps_shift_factors, aps_scale,
                                      aps_unscale)
    from cpd_tpu.parallel.ring import ring_oracle_sum, ring_transport_bytes
    from cpd_tpu.quant.numerics import cast_to_format

    region, spread = 32, 40
    stacked = _frontier_probe(world, n, region=region, spread=spread)
    ref = np.asarray(ring_oracle_sum(jnp.asarray(stacked), 8, 23))

    def score(got: np.ndarray) -> dict:
        # ulp distance on the fp32 number line (monotone int encoding:
        # flip the sign-magnitude order for negatives)
        def toward(x):
            u = x.view(np.int32).astype(np.int64)
            return np.where(u < 0, np.int64(-2147483648) - u, u)
        ulp = np.abs(toward(got.copy()) - toward(ref.copy()))
        err64 = (got.astype(np.float64) - ref.astype(np.float64))
        ref64 = ref.astype(np.float64)
        # global L2 error ratio — dominated by the largest-magnitude
        # blocks, so it measures top-of-range fidelity only
        l2 = float(np.linalg.norm(err64)
                   / max(np.linalg.norm(ref64), 1e-300))
        # the headline metric: per-REGION relative L2, mean/max over
        # the probe's scale regions.  Gradients feed per-parameter
        # updates, so a small-scale layer's gradient matters relative
        # to ITS OWN magnitude — exactly the mass a single per-tensor
        # shift flushes (rel -> 1.0 for that region) and a per-block
        # shift keeps.  Region norms over 32 elements are cancellation-
        # robust, unlike per-element relative error; the global L2
        # above can't see this at all (the flushed regions are
        # individually tiny against the top blocks).
        m = (len(ref) // region) * region
        e_r = np.linalg.norm(err64[:m].reshape(-1, region), axis=1)
        r_r = np.maximum(np.linalg.norm(ref64[:m].reshape(-1, region),
                                        axis=1), 1e-300)
        return {"ulp_mean": float(np.mean(ulp)),
                "ulp_p99": float(np.percentile(ulp, 99)),
                "rel_l2": l2,
                "region_rel_l2_mean": float(np.mean(e_r / r_r)),
                "region_rel_l2_max": float(np.max(e_r / r_r))}

    rows = []
    for exp, man in formats:
        # per-tensor arm: the full APS recipe around the per-tensor ring
        # (sum_gradients' use_aps path, emulated leaf-local — the max
        # over the stacked array IS the pmax of the per-rank maxes, and
        # the ·W headroom factor matches dist_util.py:26-28)
        me = aps_max_exponents({"g": jnp.asarray(stacked)},
                               jnp.float32(world))
        shift = aps_shift_factors(me, exp)
        scaled = np.asarray(aps_scale({"g": jnp.asarray(stacked)},
                                      shift)["g"])
        q = np.asarray(cast_to_format(jnp.asarray(scaled), exp, man))
        red = ring_oracle_sum(jnp.asarray(q), exp, man)
        got = np.asarray(aps_unscale({"g": red}, shift)["g"])
        rows.append({"format": [exp, man], "block": None,
                     "wire_bytes_per_device": ring_transport_bytes(
                         n, world, exp, man),
                     **score(got)})
        for bs in blocks:
            got = np.asarray(ring_oracle_sum(jnp.asarray(stacked), exp,
                                             man, block_scale=True,
                                             block_size=bs))
            rows.append({"format": [exp, man], "block": bs,
                         "wire_bytes_per_device": ring_transport_bytes(
                             n, world, exp, man, block_size=bs),
                         **score(got)})

    def find(fmt, block):
        for r in rows:
            if tuple(r["format"]) == fmt and r["block"] == block:
                return r
        return None

    # the headline frontier point: the best e4m3 blocked row vs the
    # per-tensor e5m7 row — strictly fewer bytes AND error no worse
    frontier = None
    base = find((5, 7), None)
    if base is not None:
        cands = [r for r in rows if tuple(r["format"]) == (4, 3)
                 and r["block"] is not None
                 and r["wire_bytes_per_device"]
                 < base["wire_bytes_per_device"]
                 and r["region_rel_l2_mean"] <= base["region_rel_l2_mean"]]
        if cands:
            best = min(cands, key=lambda r: r["region_rel_l2_mean"])
            frontier = {
                "e4m3_block": best["block"],
                "e4m3_blocked_region_rel_l2": best["region_rel_l2_mean"],
                "e5m7_per_tensor_region_rel_l2": base["region_rel_l2_mean"],
                "e4m3_blocked_bytes": best["wire_bytes_per_device"],
                "e5m7_per_tensor_bytes": base["wire_bytes_per_device"],
                "bytes_ratio": round(best["wire_bytes_per_device"]
                                     / base["wire_bytes_per_device"], 3),
            }
    return {"world": world, "elements": n, "probe_region": region,
            "probe_spread_octaves": spread, "rows": rows,
            "frontier_e4m3_vs_e5m7": frontier}


def zero2_block_sweep(n: int, formats=((4, 3), (5, 2), (5, 7)),
                      blocks=(32, 128), world: int = 8) -> dict:
    """The ZeRO-2 `all_to_all` arm of the frontier (ISSUE 12 satellite):
    per-tensor-APS vs block-scaled sharded reduce-scatter, scored per
    scale region against the exact fp32 ZeRO-2 oracle on the same
    block-structured probe as `block_frontier_sweep`.

    Accuracy rides the single-device `zero2_oracle_flat` — bit-equal to
    the distributed all_to_all by the reduce-smoke gate — so no mesh is
    needed.  Bytes are the analytic per-device all_to_all wire: (W-1)
    slices of c = ceil(n/W) elements, packed code words (+ the shift
    sidecar per slice when blocked)."""
    import jax.numpy as jnp
    import numpy as np

    from cpd_tpu.parallel.zero import zero2_oracle_flat
    from cpd_tpu.quant.numerics import wire_bytes, wire_bytes_blocked

    region, spread = 32, 40
    stacked = _frontier_probe(world, n, region=region, spread=spread)
    tree = {"g": jnp.asarray(stacked)}
    c = -(-n // world)

    def reassemble(flat_ws):
        # single whole-tree bucket: rank-major (W, c) -> flat[:n]
        return np.asarray(flat_ws).reshape(-1)[:n]

    ref = reassemble(zero2_oracle_flat(tree, world)).astype(np.float64)

    def score(got):
        err = got.astype(np.float64) - ref
        m = (n // region) * region
        e_r = np.linalg.norm(err[:m].reshape(-1, region), axis=1)
        r_r = np.maximum(np.linalg.norm(ref[:m].reshape(-1, region),
                                        axis=1), 1e-300)
        return {"region_rel_l2_mean": float(np.mean(e_r / r_r)),
                "region_rel_l2_max": float(np.max(e_r / r_r))}

    rows = []
    for exp, man in formats:
        got = reassemble(zero2_oracle_flat(tree, world, use_aps=True,
                                           grad_exp=exp, grad_man=man))
        rows.append({"format": [exp, man], "block": None,
                     "wire_bytes_per_device":
                         (world - 1) * c * wire_bytes(exp, man),
                     **score(got)})
        for bs in blocks:
            got = reassemble(zero2_oracle_flat(
                tree, world, grad_exp=exp, grad_man=man,
                block_scale=True, block_size=bs))
            rows.append({"format": [exp, man], "block": bs,
                         "wire_bytes_per_device":
                             (world - 1) * wire_bytes_blocked(exp, man,
                                                              c, bs),
                         **score(got)})

    frontier = None
    base = next((r for r in rows if tuple(r["format"]) == (5, 7)
                 and r["block"] is None), None)
    if base is not None:
        cands = [r for r in rows if tuple(r["format"]) == (4, 3)
                 and r["block"] is not None
                 and r["wire_bytes_per_device"]
                 < base["wire_bytes_per_device"]
                 and r["region_rel_l2_mean"]
                 <= base["region_rel_l2_mean"]]
        if cands:
            best = min(cands, key=lambda r: r["region_rel_l2_mean"])
            frontier = {
                "e4m3_block": best["block"],
                "e4m3_blocked_region_rel_l2":
                    best["region_rel_l2_mean"],
                "e5m7_per_tensor_region_rel_l2":
                    base["region_rel_l2_mean"],
                "e4m3_blocked_bytes": best["wire_bytes_per_device"],
                "e5m7_per_tensor_bytes": base["wire_bytes_per_device"],
                "bytes_ratio": round(best["wire_bytes_per_device"]
                                     / base["wire_bytes_per_device"],
                                     3),
            }
    return {"world": world, "elements": n, "probe_region": region,
            "probe_spread_octaves": spread, "rows": rows,
            "frontier_e4m3_vs_e5m7": frontier}


def overlap_step_bench(iters: int = 8, batch_per_dev: int = 8,
                       width: int = 128, image: int = 16,
                       bucket_elems: int = 65536) -> dict:
    """Full-train-step throughput of the overlapped transport vs the
    monoliths on the current backend — the ISSUE 8 acceptance
    measurement (docs/PERF.md "Overlapped reduce").

    Arms: fp32 step (grad (8,23) — the plain-psum shortcut), faithful
    e5m2 APS (monolith), faithful+overlap, ring, ring+overlap.  The
    model is a widened TinyCNN (~320k grad elements) so the reduction is
    a real fraction of the step, as it is for ResNet-50 at pod scale.
    Pure measurement: the structural interleaving gate lives in the
    analyzer's `ir-overlap` rule now (ISSUE 14 — every
    overlap-configured registered program is checked in CI), not in
    per-arm `overlap_evidence` calls here."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cpd_tpu.models.tiny import tiny_cnn
    from cpd_tpu.parallel.dist import replicate
    from cpd_tpu.parallel.mesh import data_parallel_mesh
    from cpd_tpu.train import (create_train_state, make_optimizer,
                               make_train_step, warmup_step_decay)

    mesh = data_parallel_mesh()
    n_dev = mesh.devices.size
    model = tiny_cnn(num_classes=10, width=width)
    tx = make_optimizer("sgd", warmup_step_decay(0.1, 10, [10 ** 6]),
                        momentum=0.9)
    state = replicate(create_train_state(
        model, tx, jnp.zeros((2, image, image, 3)),
        jax.random.PRNGKey(0)), mesh)
    n_params = sum(l.size for l in jax.tree.leaves(state.params))
    rng = np.random.RandomState(0)
    gb = batch_per_dev * n_dev
    x = jnp.asarray(rng.randn(gb, image, image, 3), jnp.float32)
    y = jnp.asarray(rng.randint(0, 10, (gb,)), jnp.int32)

    arms = {
        "fp32": dict(grad_exp=8, grad_man=23, mode="faithful"),
        "faithful": dict(use_aps=True, grad_exp=5, grad_man=2,
                         mode="faithful"),
        "faithful_overlap": dict(use_aps=True, grad_exp=5, grad_man=2,
                                 mode="faithful", overlap_reduce=True,
                                 bucket_elems=bucket_elems),
        "ring": dict(use_aps=True, grad_exp=5, grad_man=2, mode="ring",
                     bucket_elems=bucket_elems),
        "ring_overlap": dict(use_aps=True, grad_exp=5, grad_man=2,
                             mode="ring", overlap_reduce=True,
                             bucket_elems=bucket_elems),
        # the arms ISSUE 12 unlocked: overlap under the emulate-node
        # micro-batch scan, and ZeRO-2 with the per-bucket in-backward
        # reduce-scatter (+ the blocked all_to_all wire)
        "faithful_overlap_emulate2": dict(
            use_aps=True, grad_exp=5, grad_man=2, mode="faithful",
            overlap_reduce=True, bucket_elems=bucket_elems,
            emulate_node=2),
        "zero2": dict(use_aps=True, grad_exp=5, grad_man=2,
                      mode="faithful", _zero2=True),
        "zero2_overlap": dict(use_aps=True, grad_exp=5, grad_man=2,
                              mode="faithful", overlap_reduce=True,
                              bucket_elems=bucket_elems, _zero2=True),
        "zero2_overlap_blocked": dict(
            use_aps=True, grad_exp=4, grad_man=3, mode="faithful",
            overlap_reduce=True, bucket_elems=bucket_elems, _zero2=True,
            block_scale=True, block_size=32),
    }
    from cpd_tpu.parallel.zero import zero2_sgd
    from cpd_tpu.train.state import TrainState
    out = {"world": n_dev, "platform": jax.devices()[0].platform,
           "grad_elements": n_params, "global_batch": gb,
           "bucket_elems": bucket_elems, "arms": {}}
    for name, kw in arms.items():
        kw = dict(kw)
        emulate = kw.get("emulate_node", 1)
        arm_state = state
        xb, yb = x, y
        if emulate > 1:
            xb = jnp.concatenate([x] * emulate)
            yb = jnp.concatenate([y] * emulate)
        if kw.pop("_zero2", False):
            z = zero2_sgd(lambda s: jnp.float32(0.05), world=n_dev,
                          momentum=0.9,
                          bucket_elems=(bucket_elems
                                        if kw.get("overlap_reduce")
                                        or "bucket_elems" in kw
                                        else None))
            arm_state, extra = z.mesh_layout(
                TrainState(step=jnp.zeros([], jnp.int32),
                           params=jax.device_get(state.params),
                           batch_stats=jax.device_get(
                               state.batch_stats),
                           opt_state=z.init(state.params)), mesh)
            step = make_train_step(model, None, mesh, donate=False,
                                   **kw, **extra)
        else:
            step = make_train_step(model, tx, mesh, donate=False, **kw)
        s, m = step(arm_state, xb, yb)
        float(m["loss"])          # compile + sync
        best = float("inf")
        for _ in range(max(1, iters)):
            t0 = now()
            s, m = step(s, xb, yb)
            float(m["loss"])
            best = min(best, now() - t0)
        out["arms"][name] = {
            "best_ms": round(best * 1e3, 3),
            "img_per_sec": round(gb * emulate / best, 1),
        }
    fp32 = out["arms"]["fp32"]["img_per_sec"]
    for name in arms:
        out["arms"][name]["vs_fp32"] = round(
            out["arms"][name]["img_per_sec"] / fp32, 3)
    return out


def smoke() -> dict:
    """CI gate (`reduce-smoke`): parity + byte-counter assertions on tiny
    sizes.  Asserts, never times — a loaded CI box must not flake it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cpd_tpu.compat import shard_map
    from cpd_tpu.parallel.mesh import make_mesh
    from cpd_tpu.parallel.ring import (gather_transport_bytes,
                                       ring_oracle_sum, ring_quantized_sum,
                                       ring_transport_bytes)

    checks = []
    rng = np.random.RandomState(7)
    key = jax.random.PRNGKey(11)
    n = 257
    for world in (2, 8):
        devices = jax.devices()[:world]
        mesh = make_mesh(dp=world, devices=devices)
        for exp, man in ((5, 2), (4, 3)):
            for kahan in (False, True):
                for k in (None, key):
                    stacked = (rng.randn(world, n) * 0.3).astype(np.float32)

                    def body(st, kahan=kahan, k=k, exp=exp, man=man):
                        return ring_quantized_sum(st[0], "dp", exp, man,
                                                  use_kahan=kahan, key=k)

                    fn = jax.jit(shard_map(body, mesh=mesh,
                                           in_specs=(P("dp"),),
                                           out_specs=P(), check_vma=False))
                    got = np.asarray(fn(jax.device_put(
                        jnp.asarray(stacked),
                        NamedSharding(mesh, P("dp")))))
                    want = np.asarray(ring_oracle_sum(
                        jnp.asarray(stacked), exp, man, use_kahan=kahan,
                        key=k))
                    label = (f"W={world} ({exp},{man}) kahan={kahan} "
                             f"sr={k is not None}")
                    if (got.view(np.uint32) != want.view(np.uint32)).any():
                        raise AssertionError(
                            f"ring != oracle (bitwise) at {label}")
                    checks.append(label)

    # verified-ring gate (ISSUE 4): the checksums must (a) pass and
    # leave the result BITWISE unchanged on a clean wire, and (b) catch
    # an injected single-bit wire flip — with exact counter values, so
    # a silently weakened checksum fails CI here
    stacked = (rng.randn(8, n) * 0.3).astype(np.float32)
    mesh8 = make_mesh(dp=8, devices=jax.devices()[:8])
    sharded = jax.device_put(jnp.asarray(stacked),
                             NamedSharding(mesh8, P("dp")))

    def vbody(st, fault=None):
        return ring_quantized_sum(st[0], "dp", 5, 2, verify=True,
                                  fault=fault)

    clean_fn = jax.jit(shard_map(vbody, mesh=mesh8, in_specs=(P("dp"),),
                                 out_specs=(P(), P()), check_vma=False))
    vec, rep = clean_fn(sharded)
    plain = np.asarray(ring_oracle_sum(jnp.asarray(stacked), 5, 2))
    if (np.asarray(vec).view(np.uint32) != plain.view(np.uint32)).any():
        raise AssertionError("verified ring != oracle on a clean wire")
    if not (int(rep["ok"]) == 1 and int(rep["hop_bad"]) == 0
            and int(rep["gather_bad"]) == 0 and int(rep["agree"]) == 1):
        raise AssertionError(f"clean verified ring reported a fault: "
                             f"{jax.tree.map(int, rep)}")

    def fbody(st):
        return vbody(st, fault=(jnp.int32(1), jnp.int32(3)))
    flip_fn = jax.jit(shard_map(fbody, mesh=mesh8, in_specs=(P("dp"),),
                                out_specs=(P(), P()), check_vma=False))
    fvec, frep = flip_fn(sharded)
    if not (int(frep["ok"]) == 0 and int(frep["hop_bad"]) == 1
            and int(frep["gather_bad"]) == 1 and int(frep["agree"]) == 0):
        raise AssertionError(f"injected wire flip not detected exactly: "
                             f"{jax.tree.map(int, frep)}")
    if (np.asarray(fvec).view(np.uint32) == plain.view(np.uint32)).all():
        raise AssertionError("injected wire flip did not corrupt the "
                             "sum — the attack is a no-op, so the "
                             "detection above proves nothing")

    # stats-cast gate (ISSUE 5): the numeric-health telemetry cast must
    # be BITWISE identical to the plain cast across formats × rounding —
    # a telemetry layer that perturbs the values it observes corrupts
    # the very training run it is supposed to protect — and its
    # counters must be exact on a crafted probe
    from cpd_tpu.quant.quant_function import (float_quantize,
                                              float_quantize_stats)
    probe = np.concatenate([
        (rng.randn(509) * (10.0 ** rng.randint(-9, 9, 509)))
        .astype(np.float32),
        np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1e-9, -2.5e-7,
                  500.0, -600.0, 240.0], np.float32)])
    key = jax.random.PRNGKey(23)
    stats_checks = 0
    for exp, man in ((4, 3), (5, 2), (5, 7), (8, 23)):
        for k in (None, key):
            rounding = "nearest" if k is None else "stochastic"
            plain = np.asarray(float_quantize(jnp.asarray(probe), exp,
                                              man, rounding=rounding,
                                              key=k))
            got, h = float_quantize_stats(jnp.asarray(probe), exp, man,
                                          rounding=rounding, key=k)
            if (np.asarray(got).view(np.uint32)
                    != plain.view(np.uint32)).any():
                raise AssertionError(
                    f"stats cast != plain cast (bitwise) at "
                    f"({exp},{man}) rounding={rounding}")
            if int(h["total"]) != probe.size or \
                    int(h["nan"]) != int(np.isnan(probe).sum()):
                raise AssertionError(
                    f"stats counters wrong at ({exp},{man}) "
                    f"rounding={rounding}: {jax.tree.map(int, h)}")
            stats_checks += 1
    # exact counts on the crafted tail at (4,3): 500/-600 saturate,
    # +/-inf pass through (4 sat), 1e-9/-2.5e-7 flush (but the random
    # head flushes more) — pin the crafted-tail contribution precisely
    _, h43 = float_quantize_stats(jnp.asarray(probe[-10:]), 4, 3)
    if {kk: int(v) for kk, v in h43.items()} != \
            {"sat": 4, "underflow": 2, "nan": 1, "total": 10}:
        raise AssertionError(
            f"(4,3) probe counters off: {jax.tree.map(int, h43)}")

    # bucketed-ring gate (ISSUE 8): per-bucket rings at the shared
    # greedy layout == per-bucket oracles at their GLOBAL offset starts
    from cpd_tpu.parallel import make_sum_gradients_fn
    from cpd_tpu.parallel.mesh import data_parallel_mesh
    mesh_dp = data_parallel_mesh()
    tree = {"a": (rng.randn(8, 37) * 0.2).astype(np.float32),
            "b": (rng.randn(8, 53) * 0.2).astype(np.float32)}
    sharded_t = jax.tree.map(
        lambda g: jax.device_put(jnp.asarray(g),
                                 NamedSharding(mesh_dp, P("dp"))), tree)
    got = jax.tree.map(np.asarray, make_sum_gradients_fn(
        mesh_dp, axis_name="dp", grad_exp=5, grad_man=2, mode="ring",
        bucket_elems=40)(sharded_t))
    for name, start in (("a", 0), ("b", 37)):
        want = np.asarray(ring_oracle_sum(jnp.asarray(tree[name]), 5, 2,
                                          offset_start=start))
        if (got[name].view(np.uint32) != want.view(np.uint32)).any():
            raise AssertionError(f"bucketed ring != oracle at leaf "
                                 f"{name}")

    # multi-axis gate (ISSUE 8): hierarchical ring on a 2D DP x TP mesh
    # == the single-device multi-axis oracle, bitwise
    from cpd_tpu.parallel.ring import (hierarchical_ring_sum,
                                       ring_oracle_sum_multi)
    mesh2d = make_mesh(dp=4, tp=2)
    st2 = (rng.randn(4, 2, 97) * 0.3).astype(np.float32)

    def h_body(st):
        return hierarchical_ring_sum(st[0, 0], ("dp", "tp"), 5, 2,
                                     key=key)

    hfn = jax.jit(shard_map(h_body, mesh=mesh2d,
                            in_specs=(P("dp", "tp"),), out_specs=P(),
                            check_vma=False))
    hgot = np.asarray(hfn(jax.device_put(
        jnp.asarray(st2), NamedSharding(mesh2d, P("dp", "tp")))))
    hwant = np.asarray(ring_oracle_sum_multi(jnp.asarray(st2), 2, 5, 2,
                                             key=key))
    if (hgot.view(np.uint32) != hwant.view(np.uint32)).any():
        raise AssertionError("2D hierarchical ring != multi-axis oracle")

    # overlap gate (ISSUE 8): the overlapped step's updated params are
    # BITWISE the monolith's.  The interleaving half of the old gate —
    # overlap_evidence's structural jaxpr probe — moved to the analyzer
    # (ISSUE 14): the `ir-overlap` rule checks every overlap-configured
    # REGISTERED program in the CI `ir-contracts` gate, one
    # implementation (overlap.evidence_from_prims) instead of ad-hoc
    # call sites here
    from cpd_tpu.models.tiny import tiny_cnn
    from cpd_tpu.parallel.dist import replicate
    from cpd_tpu.train import (create_train_state, make_optimizer,
                               make_train_step, warmup_step_decay)
    model = tiny_cnn(num_classes=4, width=4)
    tx = make_optimizer("sgd", warmup_step_decay(0.1, 10, [100]),
                        momentum=0.9)
    state0 = replicate(create_train_state(
        model, tx, jnp.zeros((2, 8, 8, 3)), jax.random.PRNGKey(0)),
        mesh_dp)
    xs = jnp.asarray(rng.randn(16, 8, 8, 3), jnp.float32)
    ys = jnp.asarray(np.arange(16) % 4, jnp.int32)
    step_kw = dict(use_aps=True, grad_exp=5, grad_man=2, mode="ring",
                   bucket_elems=100, donate=False)
    mono = make_train_step(model, tx, mesh_dp, **step_kw)
    over = make_train_step(model, tx, mesh_dp, overlap_reduce=True,
                           **step_kw)
    sa, ma = mono(state0, xs, ys)
    sb, mb = over(state0, xs, ys)
    for pa, pb in zip(jax.tree.leaves(sa.params),
                      jax.tree.leaves(sb.params)):
        if (np.asarray(pa).view(np.uint32)
                != np.asarray(pb).view(np.uint32)).any():
            raise AssertionError("overlapped step != monolith step "
                                 "(bitwise params)")
    # ---- block-scaled oracle gate (ISSUE 9): the blocked distributed
    # ring == the extended single-device oracle, BITWISE, across
    # formats x W in {2,4,8} x {RTNE, SR, Kahan} — including an odd
    # block size so the tail-block path is exercised on the wire
    blocked_checks = 0
    bs = 33
    for world in (2, 4, 8):
        devices = jax.devices()[:world]
        mesh_w = make_mesh(dp=world, devices=devices)
        for exp, man in ((5, 2), (4, 3)):
            for kahan, k in ((False, None), (False, key), (True, None)):
                stacked = _frontier_probe(world, n, seed=world)

                def bbody(st, kahan=kahan, k=k, exp=exp, man=man):
                    return ring_quantized_sum(
                        st[0], "dp", exp, man, use_kahan=kahan, key=k,
                        block_scale=True, block_size=bs)

                fn = jax.jit(shard_map(bbody, mesh=mesh_w,
                                       in_specs=(P("dp"),),
                                       out_specs=P(), check_vma=False))
                got = np.asarray(fn(jax.device_put(
                    jnp.asarray(stacked),
                    NamedSharding(mesh_w, P("dp")))))
                want = np.asarray(ring_oracle_sum(
                    jnp.asarray(stacked), exp, man, use_kahan=kahan,
                    key=k, block_scale=True, block_size=bs))
                if (got.view(np.uint32) != want.view(np.uint32)).any():
                    raise AssertionError(
                        f"blocked ring != oracle (bitwise) at W={world} "
                        f"({exp},{man}) kahan={kahan} sr={k is not None}")
                blocked_checks += 1

    # ---- fused-digest parity gate (ISSUE 9): the digests the fused
    # Pallas wire kernels emit == the standalone `integrity.wire_digest`
    # of the same wire buffers, plain and block-scaled
    from cpd_tpu.ops.quantize import hop_pack_pallas, quantize_pack_pallas
    from cpd_tpu.parallel.integrity import wire_digest
    g0 = jnp.asarray((rng.randn(300) * 0.3).astype(np.float32))
    g1 = jnp.asarray((rng.randn(300) * 0.3).astype(np.float32))
    fused_digest_checks = 0
    for blk in (None, 128):
        r0, w0, d0 = quantize_pack_pallas(g0, 5, 2, block_size=blk,
                                          want_digest=True,
                                          interpret=True)
        if int(d0) != int(wire_digest(w0)):
            raise AssertionError(f"fused hop-0 digest != wire_digest "
                                 f"(block={blk})")
        r1, w1, d_in, d_out = hop_pack_pallas(w0, g1, 5, 2,
                                              block_size=blk,
                                              want_digest=True,
                                              interpret=True)
        if int(d_in) != int(wire_digest(w0)):
            raise AssertionError(f"fused received-digest != wire_digest "
                                 f"(block={blk})")
        if int(d_out) != int(wire_digest(w1)):
            raise AssertionError(f"fused emitted-digest != wire_digest "
                                 f"(block={blk})")
        fused_digest_checks += 3

    # ...and end-to-end: the fused verified ring is clean on a clean
    # wire, catches an injected wire flip with EXACT counters, and its
    # clean result is bitwise the oracle's
    def fused_vbody(st, fault=None):
        return ring_quantized_sum(st[0], "dp", 5, 2, verify=True,
                                  fused=True, interpret=True,
                                  fault=fault)
    stacked = (rng.randn(8, n) * 0.3).astype(np.float32)
    sharded = jax.device_put(jnp.asarray(stacked),
                             NamedSharding(mesh8, P("dp")))
    fus_fn = jax.jit(shard_map(fused_vbody, mesh=mesh8,
                               in_specs=(P("dp"),),
                               out_specs=(P(), P()), check_vma=False))
    fvec2, frep2 = fus_fn(sharded)
    plain2 = np.asarray(ring_oracle_sum(jnp.asarray(stacked), 5, 2))
    if (np.asarray(fvec2).view(np.uint32) != plain2.view(np.uint32)).any():
        raise AssertionError("fused verified ring != oracle on a clean "
                             "wire")
    if not (int(frep2["ok"]) == 1 and int(frep2["hop_bad"]) == 0
            and int(frep2["gather_bad"]) == 0):
        raise AssertionError(f"clean fused verified ring reported a "
                             f"fault: {jax.tree.map(int, frep2)}")

    def fused_fbody(st):
        return fused_vbody(st, fault=(jnp.int32(1), jnp.int32(3)))
    fus_flip = jax.jit(shard_map(fused_fbody, mesh=mesh8,
                                 in_specs=(P("dp"),),
                                 out_specs=(P(), P()), check_vma=False))
    _, frep3 = fus_flip(sharded)
    if not (int(frep3["ok"]) == 0 and int(frep3["hop_bad"]) == 1
            and int(frep3["gather_bad"]) == 1
            and int(frep3["agree"]) == 0):
        raise AssertionError(f"fused verified ring missed the injected "
                             f"flip (exact counters): "
                             f"{jax.tree.map(int, frep3)}")

    # ---- blocked ZeRO-2 oracle gate (ISSUE 12 leg 1): the block-
    # scaled all_to_all reduce-scatter (pack_exmy_blocked code words +
    # shift sidecar on the wire, blocked scan casts) == the single-
    # device zero2_oracle_flat, BITWISE, per-tensor AND blocked wires,
    # RTNE/SR/Kahan — and deterministic across two runs
    from cpd_tpu.parallel.zero import zero2_oracle_flat, zero2_sgd
    z2 = zero2_sgd(lambda s: 0.1, world=8)
    z2_tree = {"g": jnp.asarray(_frontier_probe(8, 137, seed=19))}
    z2_sharded = jax.tree.map(
        lambda g: jax.device_put(g, NamedSharding(mesh8, P("dp"))),
        z2_tree)
    zero2_checks = 0
    for prec in (dict(use_aps=True, grad_exp=4, grad_man=3,
                      block_scale=True, block_size=8),
                 dict(grad_exp=5, grad_man=2, use_kahan=True,
                      block_scale=True, block_size=32),
                 dict(use_aps=True, grad_exp=4, grad_man=3,
                      block_scale=True, block_size=8,
                      rounding="stochastic", key=key)):

        def z2body(t, prec=prec):
            import jax as _jax
            local = _jax.tree.map(lambda g: g[0], t)
            sh = z2._grad_shard(local, None, "dp", **prec)
            from jax import lax as _lax
            return _lax.all_gather(sh, "dp", axis=0, tiled=True)

        z2fn = jax.jit(shard_map(z2body, mesh=mesh8,
                                 in_specs=(jax.tree.map(
                                     lambda _: P("dp"), z2_tree),),
                                 out_specs=P(), check_vma=False))
        got_a = np.asarray(z2fn(z2_sharded))
        got_b = np.asarray(z2fn(z2_sharded))
        okw = {k: v for k, v in prec.items() if k != "rounding"}
        want = np.asarray(zero2_oracle_flat(z2_tree, 8, **okw))
        if (got_a.view(np.uint32) != want.view(np.uint32)).any():
            raise AssertionError(f"blocked ZeRO-2 != oracle at {prec}")
        if (got_a.view(np.uint32) != got_b.view(np.uint32)).any():
            raise AssertionError(f"blocked ZeRO-2 nondeterministic at "
                                 f"{prec}")
        zero2_checks += 1

    # ---- fused all-gather-digest gate (ISSUE 12 leg 4): the one-pass
    # per-row digest kernel == vmap(wire_digest) on real gathered wire
    # shapes (the end-to-end fused verified ring above already runs
    # THROUGH this kernel — its clean/flip verdicts gate the wiring)
    from cpd_tpu.ops.quantize import digest_rows_pallas
    rows_probe = jnp.asarray(rng.randint(0, 256, size=(8, 1337)),
                             jnp.uint8)
    got_rows = np.asarray(digest_rows_pallas(rows_probe, True))
    want_rows = np.asarray(jax.vmap(wire_digest)(rows_probe))
    if (got_rows != want_rows).any():
        raise AssertionError("digest_rows_pallas != wire_digest rows")

    # ---- verified-ring cost gate (ISSUE 9): the digest redesign
    # (division-free Fletcher, concat-composed agreement instead of a
    # second full-vector hash, hop digests emitted BY the fused pack
    # kernel) took the verified ring from the PR-4 +449-566% to ~3.4x
    # (XLA arm) / ~1.9x (fused arm, kernel-interpret) on a SINGLE-CORE
    # CPU mesh, where every hash op serializes against the reduce
    # itself and the in-kernel digests run interpreted.  The <= 1.2x
    # target is the COMPILED-kernel claim (digest = ~6 VPU ops riding a
    # memory-bound pack kernel + O(W) scalar tag algebra; not measured
    # on the chip) — this gate pins the measured CPU bounds so a
    # regression back toward separate-pass digesting fails loudly.
    # 1M elements PER RANK: small vectors measure interpret-mode
    # per-op dispatch (fixed cost per kernel op), not the digest
    # arithmetic the bound is about
    n_big_t = 1_000_000
    big = (rng.randn(8, n_big_t) * 0.1).astype(np.float32)
    big_sh = jax.device_put(jnp.asarray(big),
                            NamedSharding(mesh8, P("dp")))

    def timed(verify, fused=False):
        # the body must RETURN the report scalars: dropping them lets
        # XLA dead-code-eliminate the whole verify computation (the
        # clean result is bitwise independent of it by design), and the
        # gate would then time the clean path twice — this gate
        # measured exactly that mistake before this comment existed
        def body(st):
            if verify:
                vec, rep = ring_quantized_sum(st[0], "dp", 5, 2,
                                              verify=True, fused=fused,
                                              interpret=fused)
                return vec, rep["ok"]
            return (ring_quantized_sum(st[0], "dp", 5, 2, fused=fused,
                                       interpret=fused),
                    jnp.ones([], jnp.int32))
        fn = jax.jit(shard_map(body, mesh=mesh8, in_specs=(P("dp"),),
                               out_specs=(P(), P()), check_vma=False))
        vec, ok = fn(big_sh)
        np.asarray(vec)
        assert int(ok) == 1
        best = float("inf")
        for _ in range(10):
            t0 = now()
            vec, ok = fn(big_sh)
            np.asarray(vec)
            np.asarray(ok)
            best = min(best, now() - t0)
        return best

    t_clean = timed(False)
    t_ver = timed(True)
    verified_ratio = t_ver / t_clean
    t_clean_f = timed(False, fused=True)
    t_ver_f = timed(True, fused=True)
    fused_ratio = t_ver_f / t_clean_f
    if verified_ratio > 4.5:
        raise AssertionError(
            f"XLA verified ring {verified_ratio:.2f}x clean (> 4.5x "
            f"bound): verify has regressed toward the old separate-"
            f"pass digesting (+449-566%)")
    # the fused bound moved 2.5 -> 3.0 in ISSUE 12: the all-gather ROW
    # digests joined the kernel side (digest_rows_pallas — no XLA wire
    # digest remains on the fused arm), and under the CPU interpreter
    # every rank pays a fixed ~2 ms pallas-call dispatch for its row
    # pass where the old XLA hash vectorized to ~1 ms total.  Measured
    # 2.1-2.6x here vs 1.9-2.0x before — pure interpret-emulation tax
    # (one fewer pass on compiled kernels, where <= 1.2x remains an
    # unmeasured claim); the bound still fails a
    # regression toward the PR-4 separate-pass digesting (+449-566%)
    if fused_ratio > 3.0:
        raise AssertionError(
            f"fused verified ring {fused_ratio:.2f}x fused clean "
            f"(> 3.0x bound): the in-kernel digest path has regressed")

    # ---- frontier gate (ISSUE 9 acceptance): e4m3 block-scaled beats
    # per-tensor e5m7 at strictly fewer wire bytes on the structured
    # probe (the --block-sweep table's headline pair, small-n here)
    fr = block_frontier_sweep(4096, formats=((4, 3), (5, 7)),
                              blocks=(32, 128))
    if fr["frontier_e4m3_vs_e5m7"] is None:
        raise AssertionError(
            f"no e4m3-blocked row dominates per-tensor e5m7: "
            f"{fr['rows']}")

    # byte-counter invariants — the acceptance gate: >= 2x fewer wire
    # bytes at W=8 for e5m2 vs the faithful gather path (both flavors)
    n_big = 1_000_000
    ring_b = ring_transport_bytes(n_big, 8, 5, 2)
    gather_fp32 = gather_transport_bytes(n_big, 8, 5, 2, compressed=False)
    gather_packed = gather_transport_bytes(n_big, 8, 5, 2, compressed=True)
    assert ring_b * 2 <= gather_packed <= gather_fp32, \
        (ring_b, gather_packed, gather_fp32)
    # exact analytic forms: gather (W-1)*n*4 raw; ring 2*(W-1)*(n/W)*1
    assert gather_fp32 == 7 * n_big * 4
    assert ring_b == 2 * 7 * 125_000 * 1
    return {"parity_checks": len(checks),
            "verified_ring": {"clean_ok": True, "flip_detected": True,
                              "flip_hop_bad": int(frep["hop_bad"]),
                              "flip_gather_bad": int(frep["gather_bad"]),
                              "clean_ms": round(t_clean * 1e3, 3),
                              "verified_ms": round(t_ver * 1e3, 3),
                              "verified_over_clean":
                                  round(verified_ratio, 3),
                              "fused_clean_ms": round(t_clean_f * 1e3, 3),
                              "fused_verified_ms": round(t_ver_f * 1e3, 3),
                              "fused_verified_over_clean":
                                  round(fused_ratio, 3)},
            "block_scaled": {
                "oracle_checks": blocked_checks,
                "fused_digest_checks": fused_digest_checks,
                "fused_clean_ok": True, "fused_flip_detected": True,
                "frontier_e4m3_vs_e5m7": fr["frontier_e4m3_vs_e5m7"]},
            "zero2_blocked_oracle_checks": zero2_checks,
            "gather_digest_kernel_parity": True,
            "stats_cast_bitwise_checks": stats_checks,
            "bucketed_ring_oracle": True,
            "hierarchical_ring_2d_oracle": True,
            # interleaving verdicts moved to the analyzer's ir-overlap
            # rule (ISSUE 14) — value parity stays gated here
            "overlap": {"bitwise_vs_monolith": True},
            "ring_bytes_w8_e5m2": ring_b,
            "gather_bytes_w8_e5m2_fp32": gather_fp32,
            "gather_bytes_w8_e5m2_packed": gather_packed,
            "ring_vs_gather_fp32_ratio": round(gather_fp32 / ring_b, 2),
            "ring_vs_gather_packed_ratio": round(gather_packed / ring_b, 2)}


def main():
    # env mutation ONLY on CLI entry, never at import
    _ensure_multidevice()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-size parity + byte-counter assertions "
                         "(CI `reduce-smoke`); no timing")
    ap.add_argument("--elements", type=int, default=1_000_000)
    ap.add_argument("--exp", type=int, default=5)
    ap.add_argument("--man", type=int, default=2)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--kahan", action="store_true")
    ap.add_argument("--rounding", default="nearest",
                    choices=["nearest", "stochastic"])
    ap.add_argument("--bucket-elems", type=int, default=None,
                    help="per-bucket element cap for the bucketed "
                         "faithful gather and the bucketed ring")
    ap.add_argument("--bucket-sweep", default=None, metavar="N1,N2,..",
                    help="time the bucketed faithful/ring transports at "
                         "each comma-listed bucket size ('0' = one "
                         "whole-tree bucket); ISSUE 8's tuning table")
    ap.add_argument("--block-scale", action="store_true",
                    help="time the ring arms over the block-scaled "
                         "sidecar wire (--block-size per scale block)")
    ap.add_argument("--block-size", default=128, type=int)
    ap.add_argument("--block-sweep", default=None, nargs="?",
                    const="16,32,64,128,256", metavar="B1,B2,..",
                    help="accuracy-vs-wire-bytes frontier: per-tensor "
                         "APS vs block-scaled at each block size, "
                         "scored against the exact fp32 ring oracle "
                         "(ISSUE 9's docs/PERF.md table; default "
                         "blocks 16,32,64,128,256)")
    ap.add_argument("--overlap-bench", action="store_true",
                    help="full-train-step throughput: fp32 vs faithful "
                         "vs faithful+overlap vs ring vs ring+overlap "
                         "(the docs/PERF.md 'Overlapped reduce' table)")
    args = ap.parse_args()

    if args.smoke:
        out = {"reduce_smoke": smoke(), "status": "ok"}
    elif args.bucket_sweep:
        sizes = [None if s.strip() in ("0", "none") else int(s)
                 for s in args.bucket_sweep.split(",") if s.strip()]
        out = {"bucket_sweep": bucket_sweep(args.elements, args.exp,
                                            args.man, args.iters, sizes)}
    elif args.block_sweep:
        blocks = tuple(int(s) for s in args.block_sweep.split(",")
                       if s.strip())
        out = {"block_sweep": block_frontier_sweep(args.elements,
                                                   blocks=blocks),
               # the ZeRO-2 all_to_all arm (ISSUE 12): same probe,
               # sharded reduce-scatter wire — smaller n (the oracle
               # loops W x W sender/shard pairs on one device)
               "zero2_block_sweep": zero2_block_sweep(
                   min(args.elements, 65536), blocks=blocks)}
    elif args.overlap_bench:
        out = {"overlap_step_bench": overlap_step_bench(
            iters=args.iters)}
    else:
        out = {"reduction": measure(args.elements, args.exp, args.man,
                                    args.iters, args.kahan, args.rounding,
                                    bucket_elems=args.bucket_elems,
                                    block_scale=args.block_scale,
                                    block_size=args.block_size)}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
