"""Time one layer's `flash_gqa` call on the chip at the two LM cells' shapes.

The forward (`_flash_gqa_fwd_call`: the kernel and the layout passes
around it) and the Pallas backward (`_flash_gqa_bwd_call`: both kernels
and theirs, with each kernel's output alone besides), each at its rule's
own block lengths and at a sweep of others, and the chunked XLA gradient
(`jax.vjp` of `_chunked_attention`, what `flash_gqa`'s backward was
before PR 31; a sequence at a time at the Moonlight shape, as the model
took it).  These are the numbers in `ops/flash_gqa.py`'s comment on the
block lengths and in PERF.md section 6, PR 31 and PR 33:

    python tools/bench_flash_gqa.py [chunked] [sweep] [fwd | bwd]

(`fwd` or `bwd`: that pass's timings only; default both.)

Refuses any backend but a TPU (`ops.require_tpu`, exit 2).  bf16, causal.
One JSON object on the last line, and in chiprun_out/bench_flash_gqa.json.
"""

from __future__ import annotations

import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# (batch, tokens, heads, kv heads, D, Dv) and the (bq, bk) pairs to time,
# the forward's and the backward's; a rule's own pair is timed first
# whatever these list
SHAPES = {
    "moonlight": ((2, 8192, 16, 16, 192, 128),
                  [(1024, 2048), (2048, 1024), (512, 1024), (1024, 512),
                   (512, 512), (1024, 256), (1024, 128)],
                  [(1024, 512), (512, 1024), (512, 512), (2048, 512),
                   (2048, 1024), (256, 1024), (256, 512), (512, 256),
                   (256, 256), (1024, 128)]),
    "starcoder2": ((2, 4096, 24, 2, 128, 128),
                   [(256, 1024), (128, 2048), (256, 512), (128, 512),
                    (256, 256), (128, 256), (128, 128)],
                   [(256, 512), (256, 256), (256, 1024), (128, 1024),
                    (128, 256), (256, 128), (128, 128)]),
}


def _ms(fn, *args, n=5):
    import jax
    from cpd_tpu.obs.timing import now
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t = now()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((now() - t) / n * 1e3, 3)


def _jit_fwd(fg):
    import jax
    return jax.jit(lambda q, k, v: fg._flash_gqa_fwd_call(
        q, k, v, True, False))


def _jit_bwd(fg, pick):
    """The backward call, or the part of (dq, dk, dv) that `pick` takes,
    the rest left to the compiler to drop."""
    import jax
    return jax.jit(lambda q, k, v, o, lse, g: fg._flash_gqa_bwd_call(
        q, k, v, o, lse, g, True, False)[pick])


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from cpd_tpu.ops import require_tpu
    from cpd_tpu.ops.attention import _chunked_attention
    from cpd_tpu.utils import enable_compile_cache
    import cpd_tpu.ops.flash_gqa  # noqa: F401  (the attribute is a function)
    fg = sys.modules["cpd_tpu.ops.flash_gqa"]

    phases = sys.argv[1:] or ["chunked", "sweep"]
    dev = require_tpu("bench_flash_gqa")[0]
    enable_compile_cache()
    out = {"device": dev.device_kind}
    fwd_rule, bwd_rule = fg._fwd_blocks, fg._bwd_blocks
    sweep = "sweep" in phases
    passes = [x for x in ("fwd", "bwd") if x in phases] or ["fwd", "bwd"]

    def rec(name, fn):
        try:
            out[name] = fn()
        except Exception as e:  # noqa: BLE001 — a pair Mosaic refuses
            # must not hide the others' timings
            out[name] = "ERR " + " ".join(str(e).split())[:200]
        print(name, out[name], flush=True)

    for name, ((b, t, h, hkv, d, dv), fwd_pairs, pairs) in SHAPES.items():
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (b, t, h, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, t, hkv, dv), jnp.bfloat16)
        g = jax.random.normal(ks[3], (b, t, h, dv), jnp.bfloat16)
        own = fwd_rule(h // hkv, t, t)
        for pair in ([own] + (fwd_pairs if sweep else [])
                     if "fwd" in passes else []):
            # the lengths are read when the call is traced
            fg._fwd_blocks = lambda *a, pair=pair: pair
            jax.clear_caches()
            fwd = _jit_fwd(fg)
            rec(f"{name}_fwd_{pair[0]}x{pair[1]}"
                + ("_rule" if pair == own else "") + "_ms",
                lambda: _ms(fwd, q, k, v, n=20))
        fg._fwd_blocks = fwd_rule
        if "bwd" not in passes:
            continue
        jax.clear_caches()
        o, lse = _jit_fwd(fg)(q, k, v)

        if "chunked" in phases:
            one = lambda q, k, v: _chunked_attention(q, k, v, True, 0, 0)

            def chunked_bwd(q, k, v, g):
                def per(x):
                    qq, kk, vv, gg = (y[None] for y in x)
                    return tuple(z[0] for z in
                                 jax.vjp(one, qq, kk, vv)[1](gg))
                if name == "moonlight":
                    return lax.map(per, (q, k, v, g))
                return jax.vjp(one, q, k, v)[1](g)

            rec(f"{name}_bwd_chunked_ms",
                lambda: _ms(jax.jit(chunked_bwd), q, k, v, g, n=3))

        own = bwd_rule(h // hkv, t, t)
        for pair in [own] + (pairs if sweep else []):
            fg._bwd_blocks = lambda *a, pair=pair: pair
            jax.clear_caches()
            tag = (f"{name}_bwd_pallas_{pair[0]}x{pair[1]}"
                   + ("_rule" if pair == own else ""))
            for part, pick in (("", slice(None)), ("_dq_only", slice(0, 1)),
                               ("_dkv_only", slice(1, 3))):
                call = _jit_bwd(fg, pick)
                rec(tag + part + "_ms",
                    lambda: _ms(call, q, k, v, o, lse, g))
        fg._bwd_blocks = bwd_rule

    out_dir = os.path.join(_REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "bench_flash_gqa.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
