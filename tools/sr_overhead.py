"""Measure the stochastic-rounding faithful-reduction overhead (VERDICT
r4 ask #8): step time of rounding='stochastic' vs 'nearest' through the
faithful APS all-reduce at the ResNet-50 parameter count.

`numerics.py` (sr_bits_at docstring) claims the ~2 threefry evaluations
per element per cast site are negligible next to the gather + ordered
scan; this pins the claim with a number — on the 8-device virtual CPU
mesh, which it always uses.  That is a PROXY: threefry throughput and
gather cost both differ on TPU, where the ratio is not measured
(ROADMAP S7).

Usage:  python tools/sr_overhead.py [n_params]   (default 25.6e6)
Prints one JSON line {n_params, world, t_nearest_ms, t_sr_ms, ratio}.
"""

from __future__ import annotations

import json
import os
import re
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from cpd_tpu.obs.timing import now  # noqa: E402  (the one clock; jax-free)

# the 8-device virtual mesh, BEFORE jax import (same pattern as
# tools/pp_tax.py): without it the ordered scan degenerates to one
# accumulation step and the ratio measures nothing
os.environ["JAX_PLATFORMS"] = "cpu"
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
               os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=8").strip()


def main() -> int:
    import jax

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from cpd_tpu.compat import shard_map
    from cpd_tpu.parallel.dist import grad_sr_key, sum_gradients
    from cpd_tpu.parallel.mesh import make_mesh

    n = int(float(sys.argv[1])) if len(sys.argv) > 1 else 25_600_000
    if n < 100_000:
        raise SystemExit(f"n_params {n} too small for the leaf layout; "
                         "use >= 1e5")
    world = len(jax.devices())
    mesh = make_mesh(dp=world)
    # ResNet-50-shaped pytree: a few large conv-like leaves + small ones
    # (leaf structure matters: per-leaf gathers + leaf-offset SR indexing)
    sizes, rem = [], n
    for frac in (0.4, 0.3, 0.15, 0.1):
        sizes.append(int(n * frac))
        rem -= sizes[-1]
    sizes += [rem - 2048, 1024, 1024]
    rng = np.random.RandomState(0)
    grads = {f"leaf{i}": jnp.asarray(rng.randn(s).astype(np.float32))
             for i, s in enumerate(sizes)}

    def run(rounding, key):
        def body(g):
            return sum_gradients(g, "dp", use_aps=True, grad_exp=5,
                                 grad_man=2, mode="faithful",
                                 rounding=rounding, key=key)
        fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False))
        out = fn(grads)                      # compile + warm
        jax.block_until_ready(out)
        reps = 3
        t0 = now()
        for _ in range(reps):
            out = fn(grads)
        jax.block_until_ready(out)
        return (now() - t0) / reps * 1e3

    t_near = run("nearest", None)
    key = grad_sr_key(0, jnp.zeros([], jnp.int32), 1)
    t_sr = run("stochastic", key)
    print(json.dumps({
        "n_params": n, "world": world,
        "platform": jax.devices()[0].platform,
        "t_nearest_ms": round(t_near, 1), "t_sr_ms": round(t_sr, 1),
        "ratio": round(t_sr / t_near, 3)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
