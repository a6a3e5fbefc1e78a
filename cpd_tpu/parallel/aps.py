"""APS — Auto Precision Scaling (the paper's core contribution).

TPU-native re-implementation of reference `sum_gradients`'s APS pre/post
scaling (CPDtorch/utils/dist_util.py:22-51).  Per gradient tensor:

    max_exp      = ceil(log2(max |g * world_size|))          (dist_util.py:26-28)
    max_exp      = all_reduce(max_exp, MAX)                  (dist_util.py:29-30)
    shift_factor = (2^(exp-1) - 1) - max_exp                 (dist_util.py:32-34)
    g            = quantize(g * 2^shift_factor, exp, man)    (dist_util.py:35-37)
    ... low-precision reduction ...
    g            = g / 2^shift_factor                        (dist_util.py:44-45)

Effect: the summed gradient's exponent range is shifted to the top of the
eXmY representable range so the low-precision sum loses no dynamic range.
Scaling by exact powers of two is lossless in binary floating point, so the
shift itself introduces no rounding.

Differences from the reference, by design:

* Vectorized: all per-parameter max-exponents are computed in one fused pass
  and reduced with ONE `pmax` collective, instead of the reference's Python
  loop with a host round-trip per parameter (dist_util.py:26-34).
* All-zero gradients: the reference computes log2(0) = -inf, giving an
  infinite shift and NaN gradients (dist_util.py:27 has no guard; the
  *emulate-node* path does guard, mix.py:267-268).  We adopt the guarded
  behavior everywhere: zero tensors get shift_factor = 0.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import scopes

__all__ = ["aps_max_exponents", "aps_shift_factors",
           "aps_shift_factors_checked", "aps_scale", "aps_unscale",
           "exp2_exact"]


def exp2_exact(s: jnp.ndarray) -> jnp.ndarray:
    """The IEEE fp32 value of ``2.0 ** s`` for integer-valued float32
    ``s``, built by BIT ASSEMBLY — normals for s in [-126, 127], exact
    subnormals down to 2^-149, +inf above 127, +0.0 below -149.

    Exists because XLA:CPU lowers ``jnp.exp2`` to a polynomial that is
    off by an ulp for MOST negative integer inputs (measured: 221 of the
    254 integers in [-126, 127]), and the ulp it lands on can differ
    between compiled programs — so any bitwise contract between two
    programs that both scale by "2^shift" (the replicated reduce vs the
    ZeRO-2 shard, the monolith vs the overlap taps, a distributed path
    vs its single-device oracle) held only by luck.  Bit assembly is
    exact and program-independent by construction (ISSUE 12; the same
    doctrine as numerics._pow2 / the frexp-based blocked codec).
    Non-integer inputs are a caller error (truncated toward the
    assembled exponent)."""
    s = jnp.asarray(s, jnp.float32)
    si = jnp.clip(s, -150.0, 128.0).astype(jnp.int32)
    norm = lax.bitcast_convert_type(
        ((jnp.clip(si, -126, 127) + 127) << 23).astype(jnp.uint32),
        jnp.float32)
    sub = lax.bitcast_convert_type(
        (jnp.uint32(1) << jnp.clip(si + 149, 0, 22).astype(jnp.uint32)),
        jnp.float32)
    out = jnp.where(si >= -126, norm, sub)
    out = jnp.where(si >= 128, jnp.float32(jnp.inf), out)
    return jnp.where(si <= -150, jnp.float32(0.0), out)


def _ceil_log2_exact(m: jnp.ndarray) -> jnp.ndarray:
    """Exact ``ceil(log2(m))`` for a positive finite fp32 scalar, from
    the bit pattern (frexp): m = f·2^e with f in [0.5, 1) gives
    log2(m) in [e-1, e), so ceil = e unless m IS the power of two
    2^(e-1) (f == 0.5), where ceil = e-1.  Subnormal m pre-scales by
    2^24 exactly (frexp mis-reports subnormals on some backends).
    The transcendental it replaces could return either side of an
    integer boundary depending on the compiled program, and the
    downstream ``ceil`` turned that ulp into a whole shift unit."""
    is_sub = m < jnp.float32(2.0) ** -126
    mn = jnp.where(is_sub, m * jnp.float32(16777216.0), m)
    f, e = jnp.frexp(mn)
    ex = (e.astype(jnp.float32)
          - (f == jnp.float32(0.5)).astype(jnp.float32)
          - jnp.where(is_sub, jnp.float32(24.0), jnp.float32(0.0)))
    ex = jnp.where(m == 0, -jnp.inf, ex)
    ex = jnp.where(jnp.isinf(m), jnp.inf, ex)
    return jnp.where(jnp.isnan(m), jnp.nan, ex)


@jax.named_scope(scopes.APS_MAX_EXP)
def aps_max_exponents(grads: Any, world_size) -> jnp.ndarray:
    """ceil(log2(max|g * W|)) per leaf, stacked into one (n_leaves,) vector
    (computed EXACTLY from the max's bit pattern — `_ceil_log2_exact` —
    so every program derives the same shift from the same max).

    -inf marks an all-zero leaf (caller maps it to shift 0)."""
    leaves = jax.tree_util.tree_leaves(grads)
    w = jnp.float32(world_size)
    return jnp.stack(
        [_ceil_log2_exact(jnp.max(jnp.abs(g.astype(jnp.float32) * w)))
         for g in leaves])


def aps_shift_factors_checked(max_exp: jnp.ndarray,
                              grad_exp: int) -> tuple:
    """shift = (2^(exp-1)-1) - max_exp, distinguishing the two ways
    `max_exp` can be non-finite.

    * ``-inf`` — an all-zero leaf (log2(0)); shift 0 is CORRECT there
      (nothing to scale; the reference's guarded emulate-node path,
      mix.py:267-268).
    * ``+inf`` or ``NaN`` — the leaf itself contains Inf/NaN gradients.
      Shift 0 is merely *damage control*: the garbage value still rides
      the quantized reduce (the cast passes Inf/NaN through), so the
      condition must be SURFACED, not silently normalized away.

    Returns ``(shifts, bad)`` where ``bad`` is the int32 count of
    non-finite-gradient leaves (the ``+inf``/NaN case only — all-zero
    leaves are healthy).  `sum_gradients(stats=True)` exposes it as the
    ``aps_bad`` counter, which the grad guard's skip and the precision
    supervisor (resilience/precision.py) both see.  Call on the
    pmax-agreed vector: the verdict is then replicated by construction
    (pmax propagates +inf, and jnp.maximum propagates NaN)."""
    upper_bound = jnp.float32(2 ** (grad_exp - 1) - 1)
    shift = upper_bound - max_exp
    bad = jnp.sum((jnp.isnan(max_exp)
                   | (max_exp == jnp.inf)).astype(jnp.int32))
    return jnp.where(jnp.isfinite(shift), shift, jnp.float32(0.0)), bad


def aps_shift_factors(max_exp: jnp.ndarray, grad_exp: int) -> jnp.ndarray:
    """shift = (2^(exp-1)-1) - max_exp, with the all-zero guard (shift=0).

    Maps BOTH non-finite cases to shift 0 (see the checked variant for
    why they differ); callers that can report should prefer
    `aps_shift_factors_checked`."""
    return aps_shift_factors_checked(max_exp, grad_exp)[0]


@jax.named_scope(scopes.APS_SCALE)
def aps_scale(grads: Any, shifts: jnp.ndarray) -> Any:
    """g * 2^shift per leaf (lossless power-of-two scaling — the scale
    is the EXACT `exp2_exact` power of two, program-independent)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    scaled = [g * exp2_exact(shifts[i]) for i, g in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, scaled)


@jax.named_scope(scopes.APS_UNSCALE)
def aps_unscale(grads: Any, shifts: jnp.ndarray) -> Any:
    """g / 2^shift per leaf — a true fp32 divide like the reference
    (dist_util.py:45), NOT multiply-by-2^-shift: for shifts > 127 the
    reference's 2^shift overflows to inf and the divide flushes to 0, which
    a multiply by the subnormal 2^-shift would not reproduce.  The
    divisor is the EXACT `exp2_exact` power of two (shift > 127 still
    assembles +inf, so the documented flush-to-0 survives)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    scaled = [g / exp2_exact(shifts[i]) for i, g in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, scaled)


@jax.named_scope(scopes.APS_MAX_EXP)
def pmax_scalar_vector(vec: jnp.ndarray, axis_name: str | Sequence[str]) -> jnp.ndarray:
    """One MAX collective over the stacked per-leaf exponent vector —
    the TPU replacement for dist.all_reduce(max_exp, MAX)
    (dist_util.py:29-30), one collective instead of a host sync."""
    return lax.pmax(vec, axis_name)
