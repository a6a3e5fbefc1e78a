"""Off-chip half of the MFU question: put numbers under the "bs-32
underfills the chip" diagnosis without needing a chip.

Two independent analyses of the ResNet-50 train step (fwd+bwd), bs 32 vs
bs 128:

1. **Analytic MXU-tiling model** (hardware-independent): trace the step
   with `jax.make_jaxpr` (abstract — nothing executes), walk every
   `conv_general_dilated` / `dot_general`, convert each to its GEMM
   shape (conv im2col: M = B·OH·OW, K = KH·KW·Cin, N = Cout), and score
   MXU utilization as the fraction of the 128-padded tile volume that is
   real work: eff = MNK / (⌈M/128⌉·⌈N/128⌉·⌈K/128⌉·128³).  The
   FLOP-weighted average over the whole step is the model's ceiling on
   MXU utilization from shape padding alone.
2. **Compiled-HLO cost model** (XLA:CPU proxy): `lower().compile()
   .cost_analysis()` for both batch sizes — total FLOPs and bytes
   accessed, giving arithmetic intensity (flops/byte) to place each
   graph against the v5e roofline ridge (197e12 / 8.2e11 ≈ 240
   flops/byte).  CPU fusion differs from TPU, so intensities are a
   proxy; the RATIO bs128/bs32 is the robust signal.

Usage: python tools/mfu_model.py [--no-compile]  (compile pass on the
1-vCPU sandbox takes minutes; the analytic pass is seconds).
Prints per-shape rows then one JSON line; paste results into
docs/PERF.md.
"""

from __future__ import annotations

import json
import math
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _walk(jaxpr, out):
    for eqn in jaxpr.eqns:
        for sub in eqn.params.values():
            if hasattr(sub, "jaxpr"):
                _walk(sub.jaxpr, out)
            elif isinstance(sub, (list, tuple)):
                for s in sub:
                    if hasattr(s, "jaxpr"):
                        _walk(s.jaxpr, out)
        if eqn.primitive.name == "conv_general_dilated":
            lhs, rhs = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
            dn = eqn.params["dimension_numbers"]
            out.append(("conv", lhs, rhs, dn,
                        eqn.outvars[0].aval.shape))
        elif eqn.primitive.name == "dot_general":
            lhs, rhs = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
            dn = eqn.params["dimension_numbers"]
            out.append(("dot", lhs, rhs, dn, eqn.outvars[0].aval.shape))


def _gemm_shape(kind, lhs, rhs, dn, oshape):
    """(M, N, K) of the op's GEMM view."""
    if kind == "conv":
        # dn: ConvDimensionNumbers with lhs_spec (N, C, spatial...)
        ls, rs, _ = dn.lhs_spec, dn.rhs_spec, dn.out_spec
        b = lhs[ls[0]]
        cin = lhs[ls[1]]
        cout = rhs[rs[0]]
        k_spatial = math.prod(rhs[i] for i in rs[2:])
        out_spatial = math.prod(oshape[i] for i in dn.out_spec[2:])
        return b * out_spatial, cout, cin * k_spatial
    (lc, rc), (lb, rb) = dn
    batch = math.prod(lhs[i] for i in lb) or 1
    m = math.prod(l for i, l in enumerate(lhs)
                  if i not in lc and i not in lb) or 1
    n = math.prod(r for i, r in enumerate(rhs)
                  if i not in rc and i not in rb) or 1
    k = math.prod(lhs[i] for i in lc) or 1
    return batch * m, n, k   # fold batch into M (worst-case tiling view)


def _pad(v, t=128):
    return -(-v // t) * t


def _grad_fn(batch: int):
    """(grad_fn, params) of the ResNet-50 fwd+bwd step — the ONE
    traced/compiled graph both analyses score."""
    import jax
    import jax.numpy as jnp
    import optax

    from cpd_tpu.models import resnet50

    model = resnet50(dtype=jnp.bfloat16)
    x = jnp.zeros((batch, 224, 224, 3), jnp.bfloat16)
    y = jnp.zeros((batch,), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), x[:1])

    def loss_fn(params):
        logits, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    return jax.grad(loss_fn), variables["params"]


def analyze(batch: int):
    import jax

    grad_fn, params = _grad_fn(batch)
    jaxpr = jax.make_jaxpr(grad_fn)(params)
    ops: list = []
    _walk(jaxpr.jaxpr, ops)

    rows, tot_flops, tot_eff_flops = [], 0.0, 0.0
    for kind, lhs, rhs, dn, oshape in ops:
        m, n, k = _gemm_shape(kind, lhs, rhs, dn, oshape)
        flops = 2.0 * m * n * k
        eff = (m * n * k) / (_pad(m) * _pad(n) * _pad(k))
        tot_flops += flops
        tot_eff_flops += flops * eff
        rows.append((kind, m, n, k, flops, eff))
    return rows, tot_flops, tot_eff_flops / tot_flops


def cost_analysis(batch: int):
    import jax

    grad_fn, params = _grad_fn(batch)
    compiled = jax.jit(grad_fn).lower(params).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return {"flops": ca.get("flops"),
            "bytes": ca.get("bytes accessed")}


def main() -> int:
    import jax

    # an off-chip analysis (abstract tracing + XLA:CPU cost analysis):
    # pin the CPU backend so it never asks for a chip
    jax.config.update("jax_platforms", "cpu")

    out = {}
    for batch in (32, 128):
        rows, flops, weff = analyze(batch)
        out[f"bs{batch}"] = {
            "gemm_flops": flops,
            "mxu_tile_efficiency": round(weff, 4),
            "n_matmul_ops": len(rows),
        }
        # the worst offenders: lowest-efficiency ops weighted by FLOPs
        worst = sorted(rows, key=lambda r: r[5] * 0 + (1 - r[5]) * r[4],
                       reverse=True)[:6]
        print(f"-- bs{batch}: {len(rows)} GEMM-view ops, "
              f"{flops/1e9:.0f} GFLOP, tile-eff {weff:.3f}; "
              f"worst padded-volume losses:")
        for kind, m, n, k, fl, eff in worst:
            print(f"   {kind:4s} M={m:<8d} N={n:<5d} K={k:<6d} "
                  f"{fl/1e9:7.1f} GFLOP eff={eff:.3f}")

    if "--no-compile" not in sys.argv:
        for batch in (32, 128):
            ca = cost_analysis(batch)
            d = out[f"bs{batch}"]
            d["hlo_flops"] = ca["flops"]
            d["hlo_bytes"] = ca["bytes"]
            if ca["flops"] and ca["bytes"]:
                d["flops_per_byte"] = round(ca["flops"] / ca["bytes"], 1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
