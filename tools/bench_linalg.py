"""Quantized distributed linear-algebra benchmark + CI gate (ISSUE 15).

The linalg workload class (cpd_tpu/linalg: sharded block matmul,
CholeskyQR2, power iteration / Lanczos — docs/LINALG.md) stress-tests
`qgemm` and the quantized wire at shapes and iteration counts training
never hits.  This tool measures it and gates it:

    python tools/bench_linalg.py              # measure: timings per
        transport + the per-format accuracy-vs-wire-bytes frontier,
        ONE JSON line out
    python tools/bench_linalg.py --smoke      # the `linalg-smoke` CI
        gate: (1) sharded matmul / QR / power / Lanczos BITWISE ==
        their single-device quantized oracles on representative
        (format x transport x Kahan/SR/blocked) arms incl. a
        non-divisible-tile and a steps>chunk configuration; (2)
        measured rel-error vs the fp64 numpy oracles within the
        documented per-format bounds (REL_ERROR_BOUNDS /
        QR_ORTHO_BOUNDS / EIG_REL_BOUNDS); (3) everything
        deterministic x2 to the bit; (4) Shampoo-lite's distributed
        update BITWISE == the replicated fp32-statistics monolith
        oracle at (8,23) Kahan AND at e5m7 ring statistics, x2
        deterministic; (5) the `cpd_linalg_*` metrics family absorbs
        into the obs registry.  Exit 1 on any violation.

Accuracy numbers are recorded in docs/PERF.md "Quantized linalg".
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _ensure_multidevice():
    """Standalone runs on CPU get the 8-virtual-device platform (the
    same trick as tests/conftest.py) — must happen before jax imports."""
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

from cpd_tpu.obs.timing import now  # noqa: E402

# the one probe scale every documented bound refers to
MM_SHAPE = (24, 40, 12)      # (m, k, n), tiles (7, 9): tails everywhere
MM_TILES = (7, 9)
QR_SHAPE = (48, 8)           # tall-skinny, W=8 -> 6 local rows
EIG_N = 24                   # symmetric probe, well-separated spectrum


def _bits_eq(a, b) -> bool:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and np.array_equal(a.view(np.uint32),
                                                 b.view(np.uint32))


def _tree_bits_eq(a, b) -> bool:
    import jax
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(_bits_eq(x, y)
                                      for x, y in zip(la, lb))


def _mm_operands():
    """The matmul probe — ONE home (tests/test_linalg.py imports these
    builders, so the CI gate and the test tier validate the same probe
    the documented bounds refer to)."""
    import numpy as np
    rng = np.random.RandomState(0)
    m, k, n = MM_SHAPE
    return (rng.randn(m, k).astype(np.float32),
            rng.randn(k, n).astype(np.float32))


def _qr_operand():
    import numpy as np
    rng = np.random.RandomState(1)
    return rng.randn(*QR_SHAPE).astype(np.float32)


def _eig_operand():
    """Symmetric probe with a well-separated leading spectrum, so the
    iterative solvers' accuracy bound measures NUMERICS, not
    convergence."""
    import numpy as np
    rng = np.random.RandomState(2)
    q, _ = np.linalg.qr(rng.randn(EIG_N, EIG_N))
    spec = np.concatenate([[8.0, 4.0, 2.5],
                           np.linspace(1.0, 0.1, EIG_N - 3)])
    s = (q * spec) @ q.T
    return ((s + s.T) / 2).astype(np.float32)


def _shampoo_operands():
    """The Shampoo probe tree (shared with tests/test_linalg.py):
    (W, params_dev, stacked_dev) — a conv/linear/bias mix so
    precondable and fallback leaves both exercise."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(4)
    W = 8
    params = {"w1": rng.randn(12, 8).astype(np.float32) * 0.1,
              "conv": rng.randn(3, 3, 4, 6).astype(np.float32) * 0.1,
              "bias": rng.randn(8).astype(np.float32) * 0.1}
    stacked = {kk: (rng.randn(W, *v.shape) * 0.05).astype(np.float32)
               for kk, v in params.items()}
    return (W, {kk: jnp.asarray(v) for kk, v in params.items()},
            {kk: jnp.asarray(v) for kk, v in stacked.items()})


class _FakeState:
    """Minimal TrainState stand-in for driving `ShampooLite.update_fn`
    outside a full trainer (shared with tests/test_linalg.py)."""

    def __init__(self, params, opt_state):
        self.params = params
        self.opt_state = opt_state


def make_shampoo_step(sh, params_dev, stacked_dev, gkw):
    """Build the jitted distributed Shampoo update over the dp mesh —
    the ONE shard_map harness the smoke gate and tests/test_linalg.py
    share (its monolith twin is ``sh.oracle_update``).  Returns
    ``(fn, opt0)`` with ``fn(stacked_dev) -> (new_params, new_opt)``."""
    import jax
    from jax.sharding import PartitionSpec as P

    from cpd_tpu.compat import shard_map
    from cpd_tpu.parallel.mesh import data_parallel_mesh
    from cpd_tpu.train.optim import ShampooLiteState

    mesh = data_parallel_mesh()
    opt0 = sh.init(params_dev)

    def body(stk):
        local = jax.tree.map(lambda g: g[0], stk)
        return sh.update_fn(local, _FakeState(params_dev, opt0), "dp",
                            mode="faithful", **gkw)

    out_spec = (jax.tree.map(lambda _: P(), params_dev),
                ShampooLiteState(
                    P(), jax.tree.map(lambda _: P(), params_dev),
                    tuple(P() for _ in opt0.stats_l),
                    tuple(P() for _ in opt0.stats_r)))
    fn = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P("dp"), stacked_dev),),
        out_specs=out_spec, check_vma=False))
    return fn, opt0


# ---------------------------------------------------------------------------
# smoke
# ---------------------------------------------------------------------------

def smoke() -> dict:
    import jax
    import numpy as np

    from cpd_tpu.linalg import (BlockLayout, EIG_REL_BOUNDS,
                                QR_ORTHO_BOUNDS, REL_ERROR_BOUNDS,
                                block_matmul, block_matmul_oracle,
                                cholesky_qr2, cholesky_qr2_oracle,
                                lanczos_topk, lanczos_topk_oracle,
                                matmul_rel_error, power_iteration,
                                power_iteration_oracle, qr_error_metrics)
    from cpd_tpu.parallel.mesh import data_parallel_mesh, make_mesh

    t0 = now()
    out = {"matmul": {}, "qr": {}, "eigen": {}, "shampoo": {}}
    a, b = _mm_operands()
    m, k, n = MM_SHAPE
    tm, tk = MM_TILES

    # -- 1. sharded block matmul: oracle parity + error bounds ----------
    mm_arms = [
        ((5, 2), "ring", {}, (2, 4)),
        ((4, 3), "gather", dict(use_kahan=True), (2, 4)),
        ((8, 23), "ring", {}, (1, 8)),
        ((4, 3), "ring", dict(block_scale=True, block_size=8), (2, 4)),
        ((5, 7), "ring", dict(rounding="stochastic",
                              key=jax.random.PRNGKey(3)), (2, 4)),
    ]
    for fmt, red, kw, (gr, gc) in mm_arms:
        mesh = make_mesh(dp=gr, tp=gc,
                         devices=jax.devices()[:gr * gc])
        lay = BlockLayout(m, k, n, gr, gc, tm, tk)
        got = block_matmul(a, b, mesh, *fmt, reduce=red, layout=lay,
                           **kw)
        want = block_matmul_oracle(a, b, lay, *fmt, reduce=red, **kw)
        assert _bits_eq(got, want), \
            f"matmul {fmt} {red} {gr}x{gc}: sharded != oracle"
        err = matmul_rel_error(got, a, b)
        assert err <= REL_ERROR_BOUNDS[fmt], \
            f"matmul {fmt}: rel error {err:.3e} > bound " \
            f"{REL_ERROR_BOUNDS[fmt]:.1e}"
        out["matmul"][f"e{fmt[0]}m{fmt[1]}|{red}"] = {
            "bitwise_vs_oracle": True, "rel_err_fp64": round(err, 8)}
    # determinism x2 (fresh call -> fresh compile of the same program)
    fmt, red, kw, (gr, gc) = mm_arms[0]
    mesh = make_mesh(dp=gr, tp=gc, devices=jax.devices()[:gr * gc])
    lay = BlockLayout(m, k, n, gr, gc, tm, tk)
    r1 = block_matmul(a, b, mesh, *fmt, reduce=red, layout=lay, **kw)
    r2 = block_matmul(a, b, mesh, *fmt, reduce=red, layout=lay, **kw)
    assert _bits_eq(r1, r2), "matmul determinism x2 broken"
    out["matmul"]["deterministic_x2"] = True

    # -- 2. CholeskyQR2 --------------------------------------------------
    aq = _qr_operand()
    mesh8 = data_parallel_mesh()
    for fmt, red, kw in [((5, 7), "ring", {}),
                         ((4, 3), "gather", dict(use_kahan=True)),
                         ((8, 23), "ring", {})]:
        q, r = cholesky_qr2(aq, mesh8, *fmt, reduce=red, **kw)
        qo, ro = cholesky_qr2_oracle(aq, 8, *fmt, reduce=red, **kw)
        assert _bits_eq(q, qo) and _bits_eq(r, ro), \
            f"qr {fmt} {red}: sharded != oracle"
        met = qr_error_metrics(q, r, aq)
        assert met["orthogonality"] <= QR_ORTHO_BOUNDS[fmt], \
            f"qr {fmt}: orthogonality {met['orthogonality']:.3e} > " \
            f"bound {QR_ORTHO_BOUNDS[fmt]:.1e}"
        assert np.allclose(np.asarray(r), np.triu(np.asarray(r))), \
            "R is not upper-triangular"
        out["qr"][f"e{fmt[0]}m{fmt[1]}|{red}"] = {
            "bitwise_vs_oracle": True,
            **{kk: round(v, 8) for kk, v in met.items()}}

    # -- 3. power iteration / Lanczos ------------------------------------
    s = _eig_operand()
    ev = np.linalg.eigvalsh(s.astype(np.float64))[::-1]
    lam, _ = power_iteration(s, mesh8, 5, 7, iters=14)
    lo, _ = power_iteration_oracle(s, 8, 5, 7, iters=14)
    assert _bits_eq(lam, lo), "power e5m7: sharded != oracle"
    perr = abs(float(lam) - ev[0]) / abs(ev[0])
    assert perr <= EIG_REL_BOUNDS[(5, 7)], \
        f"power e5m7: eig rel error {perr:.3e} > bound"
    out["eigen"]["power|e5m7"] = {"bitwise_vs_oracle": True,
                                  "rel_err_fp64": round(perr, 8)}
    # steps > per-device chunk edge (24/8 = 3): the pad/shard path
    # training shapes never hit
    vals, vecs = lanczos_topk(s, mesh8, 5, 2, k=3, steps=8)
    valso, vecso = lanczos_topk_oracle(s, 8, 5, 2, k=3, steps=8)
    assert _bits_eq(vals, valso) and _bits_eq(vecs, vecso), \
        "lanczos e5m2: sharded != oracle"
    lerr = abs(float(vals[0]) - ev[0]) / abs(ev[0])
    assert lerr <= EIG_REL_BOUNDS[(5, 2)], \
        f"lanczos e5m2: eig rel error {lerr:.3e} > bound"
    out["eigen"]["lanczos|e5m2|steps>chunk"] = {
        "bitwise_vs_oracle": True, "rel_err_fp64": round(lerr, 8)}

    # -- 4. Shampoo-lite vs the replicated monolith oracle ---------------
    out["shampoo"] = _shampoo_smoke()

    # -- 5. cpd_linalg_* metrics family ----------------------------------
    from cpd_tpu.obs.registry import MetricsRegistry
    reg = MetricsRegistry()
    for arm, row in out["matmul"].items():
        if isinstance(row, dict):
            fmt_l, _, red_l = arm.partition("|")
            reg.absorb_linalg_counters(row, algo="matmul",
                                       fmt=fmt_l or None)
    snap = reg.as_dict()
    assert any(k.startswith("cpd_linalg_") for k in snap), snap.keys()
    out["metrics_absorbed"] = sorted(
        k for k in snap if k.startswith("cpd_linalg_"))
    out["elapsed_s"] = round(now() - t0, 1)
    return out


def _shampoo_smoke() -> dict:
    import jax.numpy as jnp

    from cpd_tpu.train.optim import shampoo_lite

    W, params_dev, stacked_dev = _shampoo_operands()
    schedule = lambda step: jnp.float32(0.1)        # noqa: E731

    def one_arm(name, stat_fmt, stat_mode, gkw):
        sh = shampoo_lite(schedule, W, momentum=0.9, weight_decay=1e-4,
                          stat_exp=stat_fmt[0], stat_man=stat_fmt[1],
                          stat_mode=stat_mode, max_precond_dim=64)
        fn, opt0 = make_shampoo_step(sh, params_dev, stacked_dev, gkw)
        p1, o1 = fn(stacked_dev)
        p2, o2 = fn(stacked_dev)
        po, oo = sh.oracle_update(stacked_dev,
                                  _FakeState(params_dev, opt0), **gkw)
        assert _tree_bits_eq(p1, p2) and _tree_bits_eq(o1, o2), \
            f"shampoo {name}: not deterministic x2"
        assert _tree_bits_eq(p1, po) and _tree_bits_eq(o1, oo), \
            f"shampoo {name}: distributed != monolith oracle"
        return {"bitwise_vs_oracle": True, "deterministic_x2": True}

    out = {}
    for name, stat_fmt, stat_mode, gkw in [
            ("fp32_kahan_ring", (8, 23), "ring",
             dict(grad_exp=8, grad_man=23, use_kahan=True)),
            ("e5m7_stats_ring", (5, 7), "ring",
             dict(grad_exp=5, grad_man=7))]:
        out[name] = one_arm(name, stat_fmt, stat_mode, gkw)
    return out


# ---------------------------------------------------------------------------
# measure / frontier
# ---------------------------------------------------------------------------

def measure(iters: int = 3) -> dict:
    """Time the three algorithms on the current backend and record the
    per-format accuracy frontier with analytic wire bytes."""
    import jax
    import numpy as np

    from cpd_tpu.linalg import (BlockLayout, cholesky_qr2, lanczos_topk,
                                make_block_matmul_fn, matmul_rel_error,
                                qr_error_metrics)
    from cpd_tpu.parallel.mesh import data_parallel_mesh, make_mesh
    from cpd_tpu.parallel.ring import (gather_transport_bytes,
                                       ring_transport_bytes)

    a, b = _mm_operands()
    m, k, n = MM_SHAPE
    aq = _qr_operand()
    s = _eig_operand()
    ev = np.linalg.eigvalsh(s.astype(np.float64))[::-1]
    mesh8 = data_parallel_mesh()
    world = len(jax.devices())
    out = {"platform": jax.devices()[0].platform, "world": world,
           "formats": {}}
    mesh = make_mesh(dp=2, tp=world // 2,
                     devices=jax.devices()[:world]) \
        if world % 2 == 0 and world > 1 else mesh8
    gc = int(mesh.shape["tp"]) if world % 2 == 0 and world > 1 else 1
    for fmt in [(8, 23), (5, 7), (4, 3), (5, 2)]:
        lay = BlockLayout(m, k, n, int(mesh.shape["dp"]), gc, *MM_TILES)
        # compiled once per format; the timing loop re-dispatches the
        # SAME jitted callable (re-jitting per call was a retrace-lint
        # finding, and it would time the tracer, not the transport)
        fn = make_block_matmul_fn(mesh, lay, *fmt, reduce="ring")
        ap, bp = lay.pack_a(a), lay.pack_b(b)
        got = lay.unpack_c(fn(ap, bp))
        np.asarray(got)                       # compile + sync
        best = float("inf")
        for _ in range(max(1, iters)):
            t0 = now()
            np.asarray(fn(ap, bp))
            best = min(best, now() - t0)
        q, r = cholesky_qr2(aq, mesh8, *fmt, reduce="ring")
        lam, _ = lanczos_topk(s, mesh8, *fmt, k=1, steps=10)
        met = qr_error_metrics(q, r, aq)
        out["formats"][f"e{fmt[0]}m{fmt[1]}"] = {
            "matmul_rel_err": round(matmul_rel_error(got, a, b), 8),
            "matmul_best_ms": round(best * 1e3, 2),
            "qr_orthogonality": round(met["orthogonality"], 8),
            "qr_residual": round(met["residual"], 8),
            "lanczos_top1_rel_err": round(
                abs(float(lam[0]) - ev[0]) / abs(ev[0]), 8),
            "ring_wire_bytes_matmul": ring_transport_bytes(
                lay.partial_elems, gc, *fmt),
            "gather_wire_bytes_matmul": gather_transport_bytes(
                lay.partial_elems, gc, *fmt),
        }
    return out


def main():
    # scoped to main() like bench_reduce's: never mutate env at import
    _ensure_multidevice()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: oracle parity + error bounds + "
                         "determinism x2 + Shampoo-lite monolith gate")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args()
    if args.smoke:
        result = {"smoke": smoke(), "ok": True}
    else:
        result = measure(iters=args.iters)
    print(json.dumps(result, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
