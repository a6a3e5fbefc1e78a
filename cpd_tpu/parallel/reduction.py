"""Ordered low-precision reduction primitives (the emulation heart of L2).

The reference's key trick (CPDtorch/utils/dist_util.py:54-89) is to emulate a
low-precision all-reduce *deterministically*: gather full-precision values from
every rank, then accumulate them **in rank order**, re-quantizing to eXmY after
every addition (optionally Kahan-compensated, every intermediate quantized).
That makes the reduction's numerics independent of the network's reduction
tree — a property `psum` cannot give, since XLA's reduction order is opaque.

Here the primitive operates on a *stacked* array ``(W, ...)`` so that exactly
the same code runs in three contexts, bit-identically:

1. real collectives: ``lax.all_gather`` inside ``shard_map`` → (W, ...);
2. cluster emulation ("emulate node", reference mix.py:251-282): micro-batch
   gradients stacked on a leading axis;
3. unit tests on a single device.

Everything is a `lax.scan` over the leading axis — sequential by construction,
which is the point: order *is* the semantics being emulated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import scopes
from ..quant.numerics import (cast_body_blocked, cast_to_format,
                              cast_to_format_sr_at, sr_bits_at)

__all__ = ["ordered_quantized_sum", "kahan_quantized_sum", "quantized_sum"]


def _make_q(exp: int, man: int, key, offsets=None, block=None):
    """Per-step quantizer factory.  key=None -> RTNE (reference semantics,
    ignores the step/site arguments).  With a PRNG key -> unbiased
    stochastic rounding with an independent bitstream per (step, site,
    element offset): the sequential accumulation stays ordered and
    deterministic-given-key, but each partial sum rounds up with
    probability equal to its discarded fraction — so sub-ulp/2
    contributions survive in expectation instead of being flushed (the
    failure mode of an un-APS'd low-precision sum).

    Per-element bits are OFFSET-indexed (numerics.sr_bits_at): `offsets`
    gives each element's global flat offset (default: leaf-local
    ``arange(size)``).  Bits therefore depend only on (key, step, site,
    offset), never on the array layout — callers that pass GLOBAL offsets
    (parallel/dist.py buckets, parallel/zero.py shards) get bitwise
    agreement with the per-leaf / replicated computation.

    ``block`` switches every cast site to the block-scaled cast
    (`numerics.cast_body_blocked`, blocks of ``block`` elements along
    the LAST axis) — the ordered-scan twin of the ring's
    `_make_hop_q(block=...)`, used by ZeRO-2's blocked reduce-scatter
    scan (parallel/zero.py) so the accumulation keeps the per-block
    dynamic range the blocked wire bought."""
    if key is None:
        if block is not None:
            return lambda x, step, site: cast_body_blocked(
                x, exp, man, block)
        rtne = functools.partial(cast_to_format, exp_bits=exp, man_bits=man)
        return lambda x, step, site: rtne(x)

    def q(x, step, site):
        k = jax.random.fold_in(jax.random.fold_in(key, step), site)
        offs = (jnp.arange(x.size, dtype=jnp.uint32).reshape(x.shape)
                if offsets is None else offsets)
        if block is not None:
            rbits = jnp.broadcast_to(sr_bits_at(k, offs), jnp.shape(x))
            return cast_body_blocked(x, exp, man, block, rbits=rbits)
        return cast_to_format_sr_at(x, exp, man, k, offs)

    return q


@jax.named_scope(scopes.REDUCE_SCAN)
def ordered_quantized_sum(stacked: jnp.ndarray, exp: int, man: int,
                          key=None, offsets=None,
                          block_size=None) -> jnp.ndarray:
    """res = 0; for g in stacked: res = quantize(res + g)   — in order.

    Mirrors reference normal_sum_gradients' gather path
    (dist_util.py:60-69): accumulation starts from zeros, and every partial
    sum is re-cast to eXmY.  `stacked` has shape (W, *leaf_shape).
    `key` switches the per-step cast to stochastic rounding; `offsets`
    overrides the per-element bit indices; `block_size` switches every
    cast to the block-scaled cast (see _make_q).
    """
    q = _make_q(exp, man, key, offsets, block=block_size)

    def step(carry, xs):
        res, i = carry
        return (q(res + xs, i, 0), i + 1), None

    (res, _), _ = lax.scan(
        step, (jnp.zeros_like(stacked[0]), jnp.zeros([], jnp.int32)),
        stacked)
    return res


@jax.named_scope(scopes.REDUCE_SCAN)
def kahan_quantized_sum(stacked: jnp.ndarray, exp: int, man: int,
                        key=None, offsets=None,
                        block_size=None) -> jnp.ndarray:
    """Rank-ordered Kahan-compensated sum with every intermediate quantized.

    Mirrors reference kahan_sum_gradients (dist_util.py:72-89):

        y = q(g - c); t = q(res + y); c = q(q(t - res) - y); res = t

    With `key`, each of the four casts draws its own SR bitstream per rank
    step (sites 0-3); `offsets` overrides the per-element bit indices;
    `block_size` switches every site to the block-scaled cast.
    """
    q = _make_q(exp, man, key, offsets, block=block_size)

    def step(carry, g):
        res, c, i = carry
        y = q(g - c, i, 0)
        t = q(res + y, i, 1)
        c = q(q(t - res, i, 2) - y, i, 3)
        return (t, c, i + 1), None

    zero = jnp.zeros_like(stacked[0])
    (res, _, _), _ = lax.scan(
        step, (zero, zero, jnp.zeros([], jnp.int32)), stacked)
    return res


def quantized_sum(stacked: jnp.ndarray, exp: int, man: int,
                  use_kahan: bool = False, key=None,
                  offsets=None, block_size=None) -> jnp.ndarray:
    """Dispatch between the plain and Kahan ordered quantized sums.

    The fp32 shortcut (exp==8, man==23 → plain sum) applies only to the
    non-Kahan path, exactly as the reference does (dist_util.py:55-59 has the
    shortcut; kahan_sum_gradients:72-89 does not).  The shortcut also makes
    `key` irrelevant there (SR at (8,23) is the identity).  ``block_size``
    (ZeRO-2's blocked reduce-scatter, parallel/zero.py) switches every
    cast site to the block-scaled cast; it is a caller error at (8,23),
    where the shortcut would silently ignore it."""
    if block_size is not None and exp == 8 and man == 23 and not use_kahan:
        raise ValueError("block_size at (8, 23): the fp32 shortcut has no "
                         "cast to block-scale")
    if use_kahan:
        return kahan_quantized_sum(stacked, exp, man, key=key,
                                   offsets=offsets, block_size=block_size)
    if exp == 8 and man == 23:
        with jax.named_scope(scopes.REDUCE_SCAN):
            return jnp.sum(stacked, axis=0)
    return ordered_quantized_sum(stacked, exp, man, key=key, offsets=offsets,
                                 block_size=block_size)


def ir_programs(reg):
    """Program-contract declarations (analysis/ir/registry.py): the
    ordered-scan primitives are the emulation heart every oracle gate
    leans on — register them bitwise-gated so an ulp-unstable
    primitive (the PR 12 exp2 class) sneaking into a cast body fails
    lint before it fails a bitwise test four layers up."""

    def _scan(use_kahan, block=None):
        def build():
            arg = jax.ShapeDtypeStruct((8, 256), jnp.float32)
            return (lambda st: quantized_sum(
                st, 5 if block is None else 4,
                2 if block is None else 3,
                use_kahan=use_kahan, block_size=block), (arg,))
        return build

    deps = ("cpd_tpu.quant.numerics", "cpd_tpu.parallel.reduction")
    reg.declare("reduce.ordered_scan[e5m2]", _scan(False),
                deps=deps, bitwise=True)
    reg.declare("reduce.kahan_scan[e5m2]", _scan(True),
                deps=deps, bitwise=True)
    reg.declare("reduce.ordered_scan[blocked-e4m3,b32]",
                _scan(False, block=32), deps=deps, bitwise=True)
