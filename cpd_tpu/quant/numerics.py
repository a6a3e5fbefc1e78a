"""Core eXmY custom-precision cast — the semantic heart of the framework.

This module re-implements, TPU-natively (pure jnp bit-twiddling, fully
vectorized, jit/vmap/grad-safe), the semantics of the reference CUDA device
function ``cast_precision`` (reference: CPDtorch/quant/quant_cuda/
float_kernel.cu:10-92).  Everything else in the framework — elementwise
quantization, the quantized-accumulator GEMM, the APS low-precision gradient
all-reduce — composes this one function.

Semantics (matching the reference exactly, with deviations documented):

* Input is IEEE FP32.  Target format has ``exp_bits`` exponent bits
  (1..8) and ``man_bits`` mantissa bits (0..23), bias ``2^(exp_bits-1)-1``.
* Inf / NaN / ±0 pass through unchanged (float_kernel.cu:17-19).
* FP32 subnormal inputs flush to +0.0 — unsigned, as the reference returns
  literal ``0`` (float_kernel.cu:87-91).
* Exponent overflow is checked *before* mantissa rounding and saturates to
  ±FP32-infinity (float_kernel.cu:24-30).  Consequently a value whose
  mantissa *rounds up* past the target max does NOT become Inf — the carry
  propagates into the exponent and the (out-of-format) value ``2^(e+1)`` is
  returned, exactly as the reference does (the TODO at float_kernel.cu:71
  acknowledges this).  We replicate it bit-for-bit: emulation fidelity
  trumps IEEE correctness.
* Normal targets: round-to-nearest-even on the 24-bit significand at bit
  position ``23 - man_bits`` (float_kernel.cu:33-49).
* Subnormal targets: the significand is right-shifted by ``1 - e_new``
  first (truncating the shifted-out bits — a deliberate double-rounding
  quirk of the reference, float_kernel.cu:52) and *then* RTNE-rounded at the
  same bit position (float_kernel.cu:56-69).  We replicate the truncating
  shift exactly.
* Deviation 1: for ``man_bits == 23`` the reference's subnormal rounding
  computes ``1 << -1`` (undefined behaviour in C).  We define it as "no
  rounding" (pure truncating shift), consistent with the normal-path
  short-circuit at float_kernel.cu:33.
* Deviation 2: shifts ≥ 32 are UB in C; we define them to produce 0 (which
  is what NVIDIA hardware funnel-shifts produce in practice).

The JAX implementation is pure: it returns a new array and never aliases its
input.  The reference kernel mutates its (contiguous) input in place
(float_kernel.cu:98, quant.cu:22-23); callers that relied on that aliasing
are rewritten functionally at the API layer (quant_function.py here).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import scopes

__all__ = ["cast_to_format", "cast_body", "cast_oracle", "max_finite",
           "cast_body_sr", "cast_to_format_sr", "cast_oracle_sr",
           "sr_bits_at", "cast_to_format_sr_at",
           "pack_exmy", "unpack_exmy", "pack_code", "unpack_code",
           "wire_bytes", "kv_page_bytes",
           "block_shifts", "cast_body_blocked", "cast_to_format_blocked",
           "pack_exmy_blocked", "unpack_exmy_blocked", "sidecar_bytes",
           "wire_bytes_blocked", "format_max_exponent",
           "quant_health", "cast_to_format_stats", "HEALTH_FIELDS",
           "FP32_EXP_BITS", "FP32_MAN_BITS"]

FP32_EXP_BITS = 8
FP32_MAN_BITS = 23


def _validate(exp_bits: int, man_bits: int) -> None:
    if not (1 <= exp_bits <= 8):
        raise ValueError(f"exp_bits must be in [1, 8], got {exp_bits}")
    if not (0 <= man_bits <= 23):
        raise ValueError(f"man_bits must be in [0, 23], got {man_bits}")


def max_finite(exp_bits: int, man_bits: int) -> float:
    """Largest value the (exp_bits, man_bits) format can represent *normally*.

    Note the reference saturates on pre-rounding exponent overflow, so the
    max *exponent field* is ``2^exp_bits - 2`` (all-ones is treated as
    reserved, float_kernel.cu:24).
    """
    _validate(exp_bits, man_bits)
    bias = (1 << (exp_bits - 1)) - 1
    e_max = ((1 << exp_bits) - 2) - bias
    sig = 2.0 - 2.0 ** (-man_bits)
    return sig * (2.0 ** e_max)


def _rtne(man: jnp.ndarray, shift: int) -> jnp.ndarray:
    """Round-to-nearest-even of an integer significand at bit `shift`.

    Mirrors the three-way branch of float_kernel.cu:33-49 / :56-69:
    round-down when the round bit is 0; round-up when the round bit is 1 and
    sticky != 0; ties resolved to even (the kept LSB).
    """
    if shift <= 0:
        return man
    half = 1 << (shift - 1)
    sticky_mask = half - 1
    keep_mask = ~((1 << shift) - 1)
    round_bit = (man & half) != 0
    sticky = (man & sticky_mask) != 0
    lsb = (man & (1 << shift)) != 0
    inc = round_bit & (sticky | lsb)
    man = jnp.where(inc, man + half, man)
    return man & keep_mask


def _pow2(e: jnp.ndarray) -> jnp.ndarray:
    """Exact fp32 power of two for integer e in [-126, 127], built by bit
    assembly (no transcendental, Mosaic/Pallas-safe)."""
    return jax.lax.bitcast_convert_type(
        ((e + 127) << 23).astype(jnp.uint32), jnp.float32)


def _with_sign(mag: jnp.ndarray, negative: jnp.ndarray) -> jnp.ndarray:
    """`mag` (>= +0) with the sign bit set where `negative` — by bit
    assembly, not ``-mag``: Mosaic on libtpu 0.0.34 lowers a float
    negation as ``0 - x``, which turns ``-(+0.0)`` into +0.0 where XLA
    and the reference give -0.0 (found on the chip, PR 21)."""
    bits = jax.lax.bitcast_convert_type(mag, jnp.uint32)
    sign = jnp.where(negative, jnp.uint32(0x80000000), jnp.uint32(0))
    return jax.lax.bitcast_convert_type(bits | sign, jnp.float32)


def _cast_core(x: jnp.ndarray, exp_bits: int, man_bits: int,
               round_fn) -> jnp.ndarray:
    """Shared cast skeleton: everything except the significand rounding step.

    `round_fn(man)` maps an integer significand to its rounded value at bit
    position ``23 - man_bits`` (already masked).  `cast_body` instantiates it
    with RTNE (`_rtne`) for reference bit-parity; `cast_body_sr` with
    stochastic add-then-truncate.  The case split, saturation, subnormal
    pre-shift and value reconstruction are identical in both."""
    _validate(exp_bits, man_bits)
    x = jnp.asarray(x, jnp.float32)

    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    exp_f = ((bits >> 23) & 0xFF).astype(jnp.int32)
    man_f = (bits & 0x007FFFFF).astype(jnp.int32)
    negative = (bits >> 31) != 0

    # Case split (float_kernel.cu:17-20, :87-91).
    passthrough = (exp_f == 0xFF) | ((exp_f == 0) & (man_f == 0))
    flush_to_zero = (exp_f == 0) & (man_f != 0)

    bias = (1 << (exp_bits - 1)) - 1
    man24 = man_f | (1 << 23)
    new_e = exp_f - 127 + bias

    # Pre-rounding saturation to +/-FP32-Inf (float_kernel.cu:24-30).
    overflow = new_e >= ((1 << exp_bits) - 1)

    # Normal-target path (float_kernel.cu:31-50): round the 24-bit
    # significand; exponent carry from rounding flows into the value via the
    # shared reconstruction below.
    man_norm = round_fn(man24)
    e_norm = exp_f - 127  # new_e - bias

    # Subnormal-target path (float_kernel.cu:51-70): truncating right shift
    # by (1 - new_e), THEN round.  Shift >= 24 wipes the significand.
    sub_shift = jnp.clip(1 - new_e, 0, 24)  # man24 < 2^24, so >>24 == 0
    man_sub = round_fn(man24 >> sub_shift)
    e_sub = 1 - bias

    is_sub = new_e <= 0
    man_out = jnp.where(is_sub, man_sub, man_norm)
    e_out = jnp.where(is_sub, e_sub, e_norm)

    # Value reconstruction (float_kernel.cu:72-86): man * 2^(e-23), split
    # into two exact power-of-two factors so the subnormal tail (2^(e-23)
    # down to 2^-149) never rounds: a in [-126, 127] carries most of the
    # scale, b in [-23, 0] finishes it.  man_out < 2^25 is exact in fp32,
    # and each multiply is exact (results are k*2^-149 with k < 2^24, all
    # representable), so this equals the reference's iterative x2 / /2 loops
    # bit-for-bit.
    e = e_out - 23
    a = jnp.clip(e, -126, 127)
    b = e - a  # 0 in the normal range; [-23, 0) deep in the subnormal range
    mag = man_out.astype(jnp.float32) * _pow2(a) * _pow2(b)
    val = _with_sign(mag, negative)

    inf = jnp.where(negative, -jnp.inf, jnp.inf).astype(jnp.float32)
    val = jnp.where(overflow, inf, val)
    val = jnp.where(flush_to_zero, jnp.float32(0.0), val)
    return jnp.where(passthrough, x, val)


def cast_body(x: jnp.ndarray, exp_bits: int, man_bits: int) -> jnp.ndarray:
    """Un-jitted cast body using only ops Mosaic supports, so the SAME code
    is the XLA implementation (via `cast_to_format`) and the Pallas kernel
    body (ops/quantize.py).  See module docstring for semantics."""
    shift = 23 - man_bits
    return _cast_core(x, exp_bits, man_bits, lambda m: _rtne(m, shift))


def _sr(man: jnp.ndarray, shift: int, rbits: jnp.ndarray) -> jnp.ndarray:
    """Stochastic rounding of an integer significand at bit `shift`.

    Adds the low `shift` random bits to the significand and truncates: the
    result rounds up with probability exactly equal to the discarded
    fraction (unbiased over uniform `rbits`).  `shift <= 0` (man_bits == 23)
    is the identity, consistent with `_rtne` and deviation 1."""
    if shift <= 0:
        return man
    keep_mask = ~((1 << shift) - 1)
    r = (rbits & jnp.uint32((1 << shift) - 1)).astype(jnp.int32)
    return (man + r) & keep_mask


def cast_body_sr(x: jnp.ndarray, exp_bits: int, man_bits: int,
                 rbits: jnp.ndarray) -> jnp.ndarray:
    """Stochastic-rounding variant of `cast_body` (beyond-reference: the
    reference CUDA kernel is nearest-only, float_kernel.cu:33-49).

    `rbits` is a uint32 array broadcastable to `x.shape`; its low
    ``23 - man_bits`` bits decide the round direction per element.  All
    non-rounding semantics (Inf/NaN/±0 passthrough, FP32-subnormal flush,
    pre-rounding saturation, the subnormal truncating pre-shift, carry past
    the format max) are IDENTICAL to the RTNE cast — same format, different
    rounding.  Passing explicit bits (instead of a PRNG key) keeps the body
    Mosaic-safe so the XLA path and the Pallas kernel are bit-comparable."""
    shift = 23 - man_bits
    rbits = jnp.broadcast_to(jnp.asarray(rbits, jnp.uint32), jnp.shape(x))
    return _cast_core(x, exp_bits, man_bits, lambda m: _sr(m, shift, rbits))


@functools.partial(jax.jit, static_argnums=(1, 2))
def cast_to_format(x: jnp.ndarray, exp_bits: int, man_bits: int) -> jnp.ndarray:
    """Cast FP32 array values into the eXmY format, vectorized.

    Pure-functional, any shape/rank; `exp_bits`/`man_bits` are static so each
    format compiles once (reference: one CUDA kernel specialization per call,
    float_kernel.cu:94-101).
    """
    return cast_body(x, exp_bits, man_bits)


@functools.partial(jax.jit, static_argnums=(1, 2))
def cast_to_format_sr(x: jnp.ndarray, exp_bits: int, man_bits: int,
                      key: jax.Array) -> jnp.ndarray:
    """Stochastically-rounded eXmY cast driven by a JAX PRNG key.

    Unbiased: E[cast_to_format_sr(x)] == x for x in the format's normal
    range (each element rounds up with probability equal to its discarded
    significand fraction).  Deterministic given (x, key)."""
    rbits = jax.random.bits(key, jnp.shape(x), jnp.uint32)
    return cast_body_sr(x, exp_bits, man_bits, rbits)


def sr_bits_at(key: jax.Array, offsets: jnp.ndarray) -> jnp.ndarray:
    """Offset-indexed SR bitstream: uint32 bits per element as a pure
    function of (key, offset) — each element's bits come from its own
    threefry stream (`fold_in(key, offset)` then one draw), NOT from its
    position inside whatever array happens to hold it.

    This is what makes the gradient pipeline's stochastic rounding
    *layout-invariant*: the same (key, offset) pair yields the same bits
    whether the element is cast per-leaf, inside a fused bucket, or on a
    ZeRO reduce-scatter shard — so a sharded reduction reproduces the
    replicated reduction's bits exactly (parallel/zero.py), and bucketed
    vs per-leaf faithful reductions are bitwise identical
    (parallel/dist.py).  Costs ~2 threefry evaluations per element per
    cast site vs ~0.5 for a shape-based `jax.random.bits` — and the
    faithful ordered scan has W+1 cast sites, so this is NOT negligible:
    `tools/sr_overhead.py` measures the SR faithful reduction at
    7.8–12.3x the RTNE faithful reduction on the world=8 CPU mesh
    (0.2M–3.2M params; docs/PERF.md "SR faithful-path overhead").  The
    TPU ratio is expected lower (vectorized threefry vs the scan's ICI
    gather) but has not been measured (ROADMAP S7).  Deployments that need cheap SR should use mode="fast"
    (one pre-/post-cast pair) or the Pallas SR kernel's hardware PRNG.

    `offsets` may be any shape; values must fit uint32 (documented limit:
    reductions over > 2^32 elements would need a wider fold)."""
    flat = jnp.reshape(jnp.asarray(offsets, jnp.uint32), (-1,))
    keys = jax.vmap(lambda o: jax.random.fold_in(key, o))(flat)
    bits = jax.vmap(lambda k: jax.random.bits(k, (), jnp.uint32))(keys)
    return bits.reshape(jnp.shape(offsets))


def cast_to_format_sr_at(x: jnp.ndarray, exp_bits: int, man_bits: int,
                         key: jax.Array, offsets: jnp.ndarray) -> jnp.ndarray:
    """Stochastically-rounded eXmY cast with offset-indexed bits.

    Like `cast_to_format_sr` but the per-element round bits are drawn by
    global element offset (`sr_bits_at`) instead of by position in
    `x.shape` — the layout-invariant variant the reduction pipeline uses.
    `offsets` must have x's shape (or broadcast to it)."""
    rbits = jnp.broadcast_to(sr_bits_at(key, offsets), jnp.shape(x))
    return cast_body_sr(x, exp_bits, man_bits, rbits)


# --------------------------------------------------------------------------
# Numeric-health telemetry (the precision supervisor's sensor layer,
# resilience/precision.py).
#
# A launch-time format choice is a bet about runtime value ranges; these
# counters are how a run notices the bet going bad WHILE it can still
# react.  `quant_health` observes one cast's (input, output) pair and
# counts the three failure signatures of the eXmY cast semantics above:
#
#   sat       — output is ±Inf: the pre-rounding exponent-overflow
#               saturation (float_kernel.cu:24-30) fired, or an Inf that
#               was already in the input passed through.  Either way the
#               format is carrying Inf — the health problem is the same.
#   underflow — a non-zero finite input came out exactly 0: the
#               fp32-subnormal flush (float_kernel.cu:87-91) or the
#               subnormal-target path rounding the whole significand
#               away.  Gradient mass silently vanishing.
#   nan       — NaN inputs (passthrough): poison already upstream of the
#               cast, counted here because the cast site is where a
#               format ladder can still re-trace before the optimizer
#               eats it.
#
# Pure observation: the caller hands in whatever the cast produced, so
# enabling telemetry CANNOT change the cast's bits (gated bitwise in
# tools/bench_reduce.py --smoke).  Counters are float32 scalars —
# exact for any count below 2^24, and immune to the int32 wrap that a
# pod-scale psum (n_params x world) or a faithful-GEMM scan total
# (5·K·M·N) would hit; at those magnitudes the ~1e-7 relative rounding
# is noise against the supervisor's rate threshold.  Summable across
# leaves, sites and replicas (lax.psum).
# --------------------------------------------------------------------------

HEALTH_FIELDS = ("sat", "underflow", "nan", "total")


def quant_health(x: jnp.ndarray, q: jnp.ndarray) -> dict:
    """{sat, underflow, nan, total} float32 scalars for one cast's input
    `x` and output `q` (see the block comment above for the exact
    definitions, including why float32 and not int32 — the pod-scale
    overflow).  `total` is the element count, so callers can turn sums
    into rates.

    Zero-ness is decided on the BIT PATTERN, not by a float compare:
    XLA's CPU backend compares under DAZ semantics, where an fp32
    subnormal == 0.0 — a value compare would both miss the
    subnormal-input flush (the reference's own flush case,
    float_kernel.cu:87-91) and falsely flag e8 formats' legitimate
    subnormal OUTPUTS as underflow."""
    x = jnp.asarray(x, jnp.float32)
    q = jnp.asarray(q, jnp.float32)
    mag = jnp.uint32(0x7FFFFFFF)
    x_nonzero = (jax.lax.bitcast_convert_type(x, jnp.uint32) & mag) != 0
    q_zero = (jax.lax.bitcast_convert_type(q, jnp.uint32) & mag) == 0
    f32 = jnp.float32
    return {
        "sat": jnp.sum(jnp.isinf(q).astype(f32)),
        "underflow": jnp.sum((q_zero & x_nonzero
                              & jnp.isfinite(x)).astype(f32)),
        "nan": jnp.sum(jnp.isnan(x).astype(f32)),
        "total": jnp.asarray(x.size, f32),
    }


@functools.partial(jax.jit, static_argnums=(1, 2))
def cast_to_format_stats(x: jnp.ndarray, exp_bits: int,
                         man_bits: int) -> tuple:
    """`cast_to_format` plus its health counters: ``(q, health)`` where
    ``q`` is BITWISE identical to the plain cast (same `cast_body`) and
    ``health`` is `quant_health(x, q)` (float32 scalars)."""
    q = cast_body(x, exp_bits, man_bits)
    return q, quant_health(x, q)


# --------------------------------------------------------------------------
# Bit-packed eXmY wire format (the transport codec of parallel/ring.py and
# the compressed all_gather / all_to_all wires in parallel/dist.py,
# parallel/zero.py).
#
# An fp32 value that came out of `cast_to_format(·, e, m)` carries only
# 1 + e + m bits of information: sign, the format's e-bit exponent field,
# and the m-bit mantissa field.  `pack_exmy` re-encodes each element into
# that code word, stored little-endian in ceil((1+e+m)/8) bytes, and
# `unpack_exmy` reconstructs the exact fp32 bit pattern.  This replaces the
# old 3-entry hardware-dtype table (e5m2/f16/bf16 only): ANY format with
# man_bits >= 2 now ships compressed — including (4,3), whose saturating
# cast produces ±Inf that float8_e4m3fn cannot represent.
#
# Code-word layout (bit 0 = LSB):   [ man (m) | exp (e) | sign (1) ]
#   exp field 0            → format subnormal: value = man · 2^(1-bias-m)
#   exp field 1..2^e-2     → normal: value = (2^m + man) · 2^(F-bias-m)
#   exp field all-ones     → specials, discriminated by the mantissa code:
#       man 0 → ±Inf (the cast's pre-round saturation output)
#       man 1 → ±2^(e_max+1), the carry-past-max value the reference cast
#               deliberately emits (module docstring; float_kernel.cu:71)
#       man 2 → NaN (canonicalized — payload bits are not format data)
# The three specials are why man_bits >= 2 is required: with m < 2 the
# all-ones block has too few codes.  (8,23) bypasses the codec entirely —
# the code word IS the fp32 bit pattern, so packing is a byte split and
# every NaN payload survives.
#
# Losslessness contract: for x in the (e, m) cast's OUTPUT set (any array
# that went through cast_to_format / cast_body_sr at the same format),
# unpack_exmy(pack_exmy(x)) == x bit-for-bit, including -0.0, format
# subnormals (which for e == 8 are fp32 subnormals), ±Inf and the carry
# value.  Values outside that set are a caller error (the low mantissa
# bits are truncated, out-of-range exponents best-effort to carry/Inf).
# --------------------------------------------------------------------------


def wire_bytes(exp_bits: int, man_bits: int) -> int:
    """Bytes per element of the packed eXmY wire format."""
    _validate(exp_bits, man_bits)
    return (1 + exp_bits + man_bits + 7) // 8


def kv_page_bytes(exp_bits: int, man_bits: int, page_size: int,
                  n_kv_heads: int, head_dim: int,
                  block_size=None, tp: int = 1) -> int:
    """Bytes of ONE layer's K+V KV-cache page in the packed eXmY codec.

    The analytic sibling of `wire_bytes` for the serving stack's paged
    KV cache (cpd_tpu/serve/kvcache.py): a page holds `page_size` token
    positions × `n_kv_heads` × `head_dim` elements for BOTH the K and V
    planes, each element one `wire_bytes(exp_bits, man_bits)` code word.
    Multiply by the layer count for a request's whole-model page cost.
    This is the one source of truth bench/docs quote for KV memory per
    format; tests pin it against the actual packed page-pool slice.
    Applies the full packed-wire validation (`_validate_wire`, incl.
    the man >= 2 special-code rule): a page count for a format the
    packed cache cannot store would be a lie.

    ``block_size`` prices the BLOCK-SCALED page (ISSUE 12): each K/V
    row (one token position's n_kv_heads·head_dim elements) carries its
    `sidecar_bytes` shift lane next to the code words — the sidecar is
    EXPLICIT here, and the test pins this against the real blocked pool
    slice so the analytics can never silently under-report KV memory.

    ``tp`` prices a head-group-sharded page (ISSUE 18): the row splits
    into ``tp`` shard-local rows of ``n_kv_heads // tp`` heads, each
    carrying its OWN blocked sidecar (scale blocks span the shard-local
    row, so the sharded page is not simply the tp=1 page — the sidecar
    count can differ).  The return is the whole-page engine-aggregate;
    divide the per-shard call (``tp=1`` on ``n_kv_heads // tp`` heads)
    out yourself for the shard slice."""
    if page_size < 1 or n_kv_heads < 1 or head_dim < 1:
        raise ValueError(
            f"page_size/n_kv_heads/head_dim must be >= 1, got "
            f"({page_size}, {n_kv_heads}, {head_dim})")
    if tp < 1 or n_kv_heads % tp != 0:
        raise ValueError(
            f"tp={tp} must be >= 1 and divide n_kv_heads={n_kv_heads}: "
            "pages shard by whole KV head groups")
    _validate_wire(exp_bits, man_bits)
    n = (n_kv_heads // tp) * head_dim      # shard-local row elements
    row = n * wire_bytes(exp_bits, man_bits)
    if block_size is not None:
        if exp_bits == 8 and man_bits == 23:
            raise ValueError("block_size at (8, 23): the fp32 byte split "
                             "has nothing to scale — no blocked page "
                             "exists to price")
        row += sidecar_bytes(n, block_size)
    return tp * 2 * page_size * row


def kv_pool_bytes(exp_bits: int, man_bits: int, page_size: int,
                  n_kv_heads: int, head_dim: int, *, n_layers: int,
                  logical_pages: int, shared_pages: int = 0,
                  block_size=None, tp: int = 1) -> dict:
    """Whole-pool KV accounting with prefix-cache dedup (ISSUE 13
    satellite): ``logical_pages`` page ids as the requests see them,
    of which ``shared_pages`` are copy-on-write references to a page
    another request (or the prefix cache) already holds — so they cost
    ZERO resident bytes.  A page id spans every layer (the pool is
    ``(L, n_pages, ...)``), hence the ``n_layers`` factor on
    `kv_page_bytes` (which prices ONE layer's K+V page, sidecar
    included under ``block_size``).

    Returns ``{page_bytes, logical_bytes, resident_bytes,
    saved_bytes}`` — the dedup-savings ledger the fleet bench
    (`bench_serve --fleet`) prices its prefix-hit sweep with.  Pinned
    against real pool slices in tests (like the PR 12 sidecar
    pricing): the analytics can never silently under-report KV
    memory.

    ``tp`` prices a head-group-sharded pool (ISSUE 18): all byte
    figures stay engine-aggregate (summed over shards), and the dict
    gains ``tp`` plus ``shard_page_bytes`` — one shard's whole-model
    page cost, what each shard device actually holds per page id."""
    if n_layers < 1:
        raise ValueError(f"n_layers must be >= 1, got {n_layers}")
    if logical_pages < 0 or not 0 <= shared_pages <= logical_pages:
        raise ValueError(
            f"need 0 <= shared_pages <= logical_pages, got "
            f"({shared_pages}, {logical_pages})")
    page = n_layers * kv_page_bytes(exp_bits, man_bits, page_size,
                                    n_kv_heads, head_dim,
                                    block_size=block_size, tp=tp)
    out = {"page_bytes": page,
           "logical_bytes": logical_pages * page,
           "resident_bytes": (logical_pages - shared_pages) * page,
           "saved_bytes": shared_pages * page}
    if tp > 1:
        out["tp"] = tp
        out["shard_page_bytes"] = n_layers * kv_page_bytes(
            exp_bits, man_bits, page_size, n_kv_heads // tp, head_dim,
            block_size=block_size)
    return out


def _validate_wire(exp_bits: int, man_bits: int) -> None:
    _validate(exp_bits, man_bits)
    if man_bits < 2 and not (exp_bits == 8 and man_bits == 23):
        raise ValueError(
            f"pack_exmy needs man_bits >= 2 (got ({exp_bits}, {man_bits})): "
            "the all-ones exponent block must hold the Inf/carry/NaN "
            "special codes; ship such formats as raw fp32 instead")


def _split_bytes(code: jnp.ndarray, n_bytes: int) -> jnp.ndarray:
    """uint32 code words -> little-endian uint8 array, one trailing axis."""
    return jnp.stack(
        [((code >> (8 * k)) & jnp.uint32(0xFF)).astype(jnp.uint8)
         for k in range(n_bytes)], axis=-1)


def _join_bytes(packed: jnp.ndarray) -> jnp.ndarray:
    """Little-endian uint8 (..., B) -> uint32 code words (...)."""
    code = jnp.zeros(packed.shape[:-1], jnp.uint32)
    for k in range(packed.shape[-1]):
        code = code | (packed[..., k].astype(jnp.uint32) << (8 * k))
    return code


def pack_code(x: jnp.ndarray, exp_bits: int, man_bits: int) -> jnp.ndarray:
    """Un-jitted pack body: fp32 values in the (exp_bits, man_bits) value
    set -> uint32 code words.  Pure bit arithmetic on ops Mosaic
    supports, so the SAME code is the XLA packer (`pack_exmy`) and the
    fused Pallas wire kernel's pack stage (ops/quantize.py) — the
    `cast_body` pattern applied to the codec."""
    _validate_wire(exp_bits, man_bits)
    x = jnp.asarray(x, jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    if exp_bits == 8 and man_bits == 23:
        return bits

    sign = (bits >> 31) & jnp.uint32(1)
    exp_f = ((bits >> 23) & jnp.uint32(0xFF)).astype(jnp.int32)
    man_f = (bits & jnp.uint32(0x007FFFFF)).astype(jnp.int32)
    bias = (1 << (exp_bits - 1)) - 1
    ones = (1 << exp_bits) - 1

    is_nan = (exp_f == 0xFF) & (man_f != 0)
    is_inf = (exp_f == 0xFF) & (man_f == 0)
    # fp32 subnormal inputs have no implicit bit and a fixed 2^-126 scale
    man24 = jnp.where(exp_f > 0, man_f | (1 << 23), man_f)
    f = jnp.where(exp_f > 0, exp_f - 127, -126) + bias

    # format-subnormal when the value sits below the format's normal range
    # OR the fp32 pattern itself is subnormal (e == 8 formats)
    is_sub = (f <= 0) | (exp_f == 0)
    # finite exponent at/above the all-ones field: the carry value
    is_carry = (~is_sub) & (exp_f != 0xFF) & (f >= ones)

    shift = jnp.clip(jnp.maximum(1 - f, 0) + (23 - man_bits), 0, 31)
    man_sub = man24 >> shift
    man_norm = man_f >> (23 - man_bits)

    exp_field = jnp.where(is_sub, 0, jnp.clip(f, 0, ones)).astype(jnp.uint32)
    man_field = jnp.where(is_sub, man_sub, man_norm).astype(jnp.uint32)
    code = (sign << (exp_bits + man_bits)) | (exp_field << man_bits) \
        | man_field
    # specials: all-ones exponent + discriminant code
    top = jnp.uint32(ones << man_bits)
    code = jnp.where(is_carry, (sign << (exp_bits + man_bits)) | top
                     | jnp.uint32(1), code)
    code = jnp.where(is_inf, (sign << (exp_bits + man_bits)) | top, code)
    code = jnp.where(is_nan, top | jnp.uint32(2), code)
    return code


@functools.partial(jax.jit, static_argnums=(1, 2))
@jax.named_scope(scopes.WIRE_PACK)
def pack_exmy(x: jnp.ndarray, exp_bits: int, man_bits: int) -> jnp.ndarray:
    """Pack fp32 values already in the (exp_bits, man_bits) value set into
    little-endian uint8 code words of shape ``x.shape + (wire_bytes(),)``."""
    return _split_bytes(pack_code(x, exp_bits, man_bits),
                        wire_bytes(exp_bits, man_bits))


def unpack_code(code: jnp.ndarray, exp_bits: int,
                man_bits: int) -> jnp.ndarray:
    """Un-jitted unpack body: uint32 code words -> the exact fp32 bit
    patterns the cast produced.  Mosaic-safe twin of `pack_code` (see
    its docstring); `unpack_exmy` and the fused hop kernel share it."""
    _validate_wire(exp_bits, man_bits)
    code = jnp.asarray(code, jnp.uint32)
    if exp_bits == 8 and man_bits == 23:
        return jax.lax.bitcast_convert_type(code, jnp.float32)

    bias = (1 << (exp_bits - 1)) - 1
    ones = (1 << exp_bits) - 1
    sign = ((code >> (exp_bits + man_bits)) & jnp.uint32(1)) != 0
    exp_field = ((code >> man_bits) & jnp.uint32(ones)).astype(jnp.int32)
    man_field = (code & jnp.uint32((1 << man_bits) - 1)).astype(jnp.int32)

    is_special = exp_field == ones
    is_sub = exp_field == 0
    # normals: (2^m + man) * 2^(F - bias - m); subnormals: man * 2^(1-bias-m)
    mantissa = jnp.where(is_sub, man_field, man_field | (1 << man_bits))
    e = jnp.where(is_sub, 1, exp_field) - bias - man_bits
    # carry special: 1 * 2^(e_max + 1); e_max + 1 = ones - bias.  For e == 8
    # that is 2^128, which the exact pow2 product below overflows to +Inf —
    # the same value the e == 8 cast itself produces in place of a carry.
    is_carry = is_special & (man_field == 1)
    mantissa = jnp.where(is_carry, 1, mantissa)
    e = jnp.where(is_carry, ones - bias, e)
    # exact two-factor power-of-two product (see _cast_core's reconstruction)
    a = jnp.clip(e, -126, 127)
    b = jnp.clip(e - a, -126, 127)
    mag = mantissa.astype(jnp.float32) * _pow2(a) * _pow2(b)
    inf = jnp.float32(jnp.inf)
    mag = jnp.where(is_special & (man_field == 0), inf, mag)
    val = _with_sign(mag, sign)
    return jnp.where(is_special & (man_field >= 2), jnp.float32(jnp.nan),
                     val)


@functools.partial(jax.jit, static_argnums=(1, 2))
@jax.named_scope(scopes.WIRE_UNPACK)
def unpack_exmy(packed: jnp.ndarray, exp_bits: int,
                man_bits: int) -> jnp.ndarray:
    """Inverse of `pack_exmy`: uint8 ``(..., wire_bytes())`` -> fp32 ``(...)``
    with the exact bit patterns the cast produced."""
    n_bytes = wire_bytes(exp_bits, man_bits)
    packed = jnp.asarray(packed, jnp.uint8)
    if packed.shape[-1] != n_bytes:
        raise ValueError(f"trailing axis {packed.shape[-1]} != "
                         f"wire_bytes({exp_bits}, {man_bits}) = {n_bytes}")
    return unpack_code(_join_bytes(packed), exp_bits, man_bits)


# --------------------------------------------------------------------------
# Block-scaled eXmY codec (EQuARX-style, PAPERS.md #2; the ring transport's
# `block_scale=` wire, parallel/ring.py).
#
# APS (parallel/aps.py) shifts exponents per-TENSOR: one shared scale for
# every element of a leaf, chosen from the global max.  A tensor whose
# blocks span very different magnitudes then wastes the format's dynamic
# range everywhere except near the max — small-magnitude regions flush.
# Block scaling shares one power-of-2 scale per BLOCK of `block_size`
# consecutive elements instead: each block's values are scaled so its own
# max sits at the format's top normal exponent, cast to (exp, man), and
# the 1-byte shift rides the wire as a sidecar lane next to the packed
# code words.  An e4m3 code word + 1/block_size sidecar bytes then covers
# the dynamic range a per-tensor e5m7 cannot — the new accuracy/bytes
# frontier point tools/bench_reduce.py --block-sweep measures.
#
# Semantics (beyond-reference — the reference has no blocked cast):
#
#   per block b (blocks along the LAST axis; the tail block may be short):
#     E_b  = floor(log2(max finite |x| in b))     (0 if no finite nonzero)
#     k_b  = clip(E_b - emax, -128, 127)          emax = max_finite's exp
#     y    = x * 2^-k_b                           (exact power-of-2 scale)
#     q_s  = cast(y, exp, man)                    (RTNE or SR)
#     q_s  = +/-max_finite where a FINITE y rounded past the format max
#            (the reference cast's carry quirk, float_kernel.cu:71 —
#            clamped HERE so the scale derivation is a fixed point: the
#            quantized block max keeps exponent emax, so re-deriving k_b
#            from the output reproduces k_b exactly, which is what makes
#            `pack_exmy_blocked` idempotent/lossless on its output set)
#     out  = q_s * 2^k_b
#
# Inf/NaN pass through the cast and ride the codec's special codes; the
# shift derivation ignores them (a block of only specials gets k_b = 0).
# Zeros are invariant under any scale, so the ring's zero padding stays
# rounding-neutral.  EVERYTHING below the fp32 normal floor — subnormal
# inputs, -0.0, inputs whose scaled form would be subnormal, and
# unscaled results that would land there — canonicalizes to +0.0: the
# reference cast's own subnormal-input flush (float_kernel.cu:87-91)
# extended to the whole class, because XLA backends FTZ/DAZ subnormals
# inconsistently across fusion boundaries (and frexp mis-reports them),
# so any blocked semantics that DISTINGUISHED patterns inside that class
# would diverge between the distributed ring and its single-device
# oracle.  With the class flushed, every surviving multiply is an exact
# normal-range product and the codec round-trip is idempotent.
#
# Sidecar lane: one uint8 per block, value k_b + 128.  Wire layout of
# `pack_exmy_blocked` (last axis): [ n * wire_bytes code bytes | n_blocks
# sidecar bytes ] — one flat uint8 lane per payload, so the ring's hop
# digest covers codes AND scales in a single pass.
# --------------------------------------------------------------------------


def format_max_exponent(exp_bits: int) -> int:
    """Exponent of `max_finite(exp_bits, ·)`: (2^e - 2) - bias."""
    _validate(exp_bits, 0)
    return ((1 << exp_bits) - 2) - ((1 << (exp_bits - 1)) - 1)


def _scale_pow2(x: jnp.ndarray, e: jnp.ndarray) -> jnp.ndarray:
    """x * 2^e for integer e in [-252, 253], applied as two sequential
    exact power-of-two factors (NEVER as a precomputed 2^e scalar, which
    for |e| > 127 would itself overflow/flush and poison the product).
    Each factor multiply is exact unless the running result crosses the
    fp32 subnormal floor or overflows — deterministic either way."""
    a = jnp.clip(e, -126, 127)
    return (x * _pow2(a)) * _pow2(jnp.clip(e - a, -126, 126))


def sidecar_bytes(n: int, block_size: int) -> int:
    """Sidecar-lane bytes for n elements at one shift byte per block."""
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    return -(-n // block_size) if n else 0


def wire_bytes_blocked(exp_bits: int, man_bits: int, n: int,
                       block_size: int) -> int:
    """Total wire bytes of one block-scaled payload of n elements: the
    packed code words plus the sidecar lane.  The analytic twin of
    `pack_exmy_blocked`'s output size (pinned against the real buffer
    in tests)."""
    _validate_wire(exp_bits, man_bits)
    return n * wire_bytes(exp_bits, man_bits) + sidecar_bytes(n, block_size)


def _flush_low(x: jnp.ndarray) -> jnp.ndarray:
    """Canonicalize the entire sub-normal-floor class — fp32 subnormals
    AND ±0.0 — to +0.0.  XLA backends FTZ/DAZ subnormals inconsistently
    across fusion boundaries (a subnormal intermediate may reach the
    next op as ±tiny in one program and as ∓0.0 in another), and frexp
    mis-reports them outright — so the blocked pipeline flushes the
    whole CLASS up front: every pattern with a zero exponent field maps
    to the same +0.0 no matter which form the backend delivered."""
    bits = jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.float32),
                                        jnp.uint32)
    low = ((bits >> 23) & jnp.uint32(0xFF)) == 0
    return jnp.where(low, jnp.float32(0.0), x)


def block_shifts(x: jnp.ndarray, exp_bits: int, man_bits: int,
                 block_size: int) -> jnp.ndarray:
    """Per-block power-of-2 shift exponents k_b (int32), blocks of
    `block_size` along the LAST axis (short tail block included).
    Shape: x.shape[:-1] + (ceil(n / block_size),).  Sub-2^-126 inputs
    count as zero (`_flush_low` — the blocked cast flushes them)."""
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    x = _flush_low(jnp.asarray(x, jnp.float32))
    n = x.shape[-1]
    nb = sidecar_bytes(n, block_size)
    mag = jnp.where(jnp.isfinite(x), jnp.abs(x), 0.0)
    pad = nb * block_size - n
    if pad:
        mag = jnp.pad(mag, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    m_b = jnp.max(mag.reshape(x.shape[:-1] + (nb, block_size)), axis=-1)
    # floor(log2(m)) via frexp (exact on normals): m = f * 2^e, f in
    # [0.5, 1)
    _, e = jnp.frexp(m_b)
    emax = format_max_exponent(exp_bits)
    k = jnp.where(m_b > 0, e.astype(jnp.int32) - 1 - emax, 0)
    return jnp.clip(k, -128, 127)


def _per_element_shifts(shifts: jnp.ndarray, n: int,
                        block_size: int) -> jnp.ndarray:
    """Broadcast (..., nb) block shifts to (..., n) element shifts."""
    rep = jnp.repeat(shifts, block_size, axis=-1)
    return rep[..., :n]


def _unscale_flush(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """q * 2^k with would-be-fp32-subnormal results flushed to +0.0 (the
    blocked cast's output flush — see the block comment; shared by the
    cast and the unpacker so both reconstruct identical bits).

    The flush condition is decided from (q, k) EXPONENT arithmetic, not
    from the product's bit pattern: XLA backends disagree about whether
    a subnormal product survives a fusion boundary (CPU FTZ), so a
    pattern test would flush on one path and miss on another — a ±0.0
    divergence between the distributed ring and its oracle.  With
    frexp(|q|) = f · 2^e (f in [0.5, 1)), |q · 2^k| < 2^-126 iff
    e + k <= -126; everything kept is then a NORMAL product of exactly
    representable factors — exact on every backend."""
    _, e = jnp.frexp(q)
    flush = (jnp.isfinite(q) & (q != 0)
             & (e.astype(jnp.int32) + k <= -126))
    out = jnp.where(flush, jnp.float32(0.0), _scale_pow2(q, k))
    # the base cast's subnormal-target rounding can emit -0.0 (a wiped
    # negative significand keeps its sign); fold it into the +0.0 class
    return _flush_low(out)


def _block_quantize(x: jnp.ndarray, exp_bits: int, man_bits: int,
                    block_size: int, rbits=None) -> tuple:
    """Shared shift-scale-cast-clamp core of the blocked cast and the
    blocked packer: returns ``(q_scaled, shifts, k_elem)`` — the
    SCALED-domain quantized values (exactly what the wire's code words
    encode), the per-block shifts, and the per-element shift broadcast.
    Sub-floor inputs (and inputs whose scaled form would be fp32-
    subnormal) flush to +0.0 FIRST, so no multiply or frexp ever sees a
    pattern a backend's FTZ could have already rewritten."""
    _validate(exp_bits, man_bits)
    x = _flush_low(jnp.asarray(x, jnp.float32))
    shifts = block_shifts(x, exp_bits, man_bits, block_size)
    k = _per_element_shifts(shifts, x.shape[-1], block_size)
    _, ex = jnp.frexp(x)
    tiny = (jnp.isfinite(x) & (x != 0)
            & (ex.astype(jnp.int32) - 1 - k <= -127))
    x = jnp.where(tiny, jnp.float32(0.0), x)
    y = _scale_pow2(x, -k)
    if rbits is None:
        q = cast_body(y, exp_bits, man_bits)
    else:
        q = cast_body_sr(y, exp_bits, man_bits, rbits)
    mf = jnp.float32(max_finite(exp_bits, man_bits))
    carry = jnp.isfinite(y) & (jnp.abs(q) > mf)
    q = jnp.where(carry, jnp.where(q > 0, mf, -mf), q)
    return q, shifts, k


def cast_body_blocked(x: jnp.ndarray, exp_bits: int, man_bits: int,
                      block_size: int, rbits=None) -> jnp.ndarray:
    """Block-scaled eXmY cast (see the block comment above): per-block
    power-of-2 scale to the format's top exponent, cast (RTNE, or SR when
    `rbits` is given — same contract as `cast_body_sr`), carry clamped to
    +/-max_finite, unscale.  The ring's blocked hop quantizer AND
    `ring_oracle_sum(block_size=...)` share this one body, so the
    distributed transport and its oracle cannot drift."""
    q, _, k = _block_quantize(x, exp_bits, man_bits, block_size, rbits)
    return _unscale_flush(q, k)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def cast_to_format_blocked(x: jnp.ndarray, exp_bits: int, man_bits: int,
                           block_size: int) -> jnp.ndarray:
    """Jitted RTNE `cast_body_blocked` (blocks along the last axis)."""
    return cast_body_blocked(x, exp_bits, man_bits, block_size)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
@jax.named_scope(scopes.WIRE_PACK)
def pack_exmy_blocked(x: jnp.ndarray, exp_bits: int, man_bits: int,
                      block_size: int) -> jnp.ndarray:
    """Quantize-and-pack into the block-scaled wire: shift, RTNE-cast
    (identity when x is already in the blocked value set — SR callers
    pre-cast with `cast_body_blocked(..., rbits)` and pack losslessly),
    pack the SCALED code words, and append the sidecar lane.

    Output (last axis): ``n * wire_bytes(exp, man)`` little-endian code
    bytes followed by ``ceil(n / block_size)`` sidecar bytes (k + 128).
    Losslessness: ``unpack_exmy_blocked(pack_exmy_blocked(x)) ==
    cast_body_blocked(x)`` bitwise, and is the identity on anything that
    already went through the blocked cast at the same (format, block) —
    the fixed-point shift derivation above is what guarantees the
    re-derived k_b matches."""
    _validate_wire(exp_bits, man_bits)
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[-1]
    q, shifts, _ = _block_quantize(x, exp_bits, man_bits, block_size)
    codes = pack_exmy(q, exp_bits, man_bits)
    codes = codes.reshape(x.shape[:-1] + (n * codes.shape[-1],))
    sidecar = (shifts + 128).astype(jnp.uint8)
    return jnp.concatenate([codes, sidecar], axis=-1)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
@jax.named_scope(scopes.WIRE_UNPACK)
def unpack_exmy_blocked(packed: jnp.ndarray, exp_bits: int, man_bits: int,
                        n: int, block_size: int) -> jnp.ndarray:
    """Inverse of `pack_exmy_blocked`: split the sidecar lane off the
    wire, decode the scaled code words, and unscale each block by its
    ridden 2^k — reproducing the blocked cast's output bit-for-bit."""
    _validate_wire(exp_bits, man_bits)
    wb = wire_bytes(exp_bits, man_bits)
    nb = sidecar_bytes(n, block_size)
    packed = jnp.asarray(packed, jnp.uint8)
    if packed.shape[-1] != n * wb + nb:
        raise ValueError(
            f"trailing axis {packed.shape[-1]} != wire_bytes_blocked("
            f"{exp_bits}, {man_bits}, n={n}, block={block_size}) = "
            f"{n * wb + nb}")
    codes = packed[..., :n * wb].reshape(packed.shape[:-1] + (n, wb))
    shifts = packed[..., n * wb:].astype(jnp.int32) - 128
    q = unpack_exmy(codes, exp_bits, man_bits)
    k = _per_element_shifts(shifts, n, block_size)
    return _unscale_flush(q, k)


def cast_oracle_sr(x: float, exp_bits: int, man_bits: int, r: int) -> float:
    """Scalar oracle for the stochastic cast: follows `cast_oracle`'s control
    flow with RTNE replaced by add-`r`-then-truncate (r in [0, 2^shift)).
    Used by tests to pin the SR semantics independently of the jnp path."""
    _validate(exp_bits, man_bits)
    s = 23 - man_bits
    if not (0 <= r < (1 << s if s > 0 else 1)):
        raise ValueError(f"r must be in [0, 2^{max(s, 0)}), got {r}")
    f = np.float32(x)
    old_num = int(np.array(f, np.float32).view(np.uint32))
    exp = (old_num & 0x7F800000) >> 23
    man = old_num & 0x007FFFFF
    true_exp = exp - 127
    if exp == 0xFF or (exp == 0x00 and man == 0):
        return float(f)
    if exp == 0:
        return 0.0
    man = man | (1 << 23)
    diy_bias = (1 << (exp_bits - 1)) - 1
    new_e = true_exp + diy_bias
    if new_e >= (1 << exp_bits) - 1:
        return float(np.inf if f > 0 else -np.inf)
    if new_e > 0:
        if man_bits != 23:
            man = (man + r) & ~((1 << s) - 1)
        new_e -= diy_bias
    else:
        shift_amt = 1 - new_e
        man = man >> shift_amt if shift_amt < 32 else 0
        new_e = 1 - diy_bias
        if man_bits != 23:
            man = (man + r) & ~((1 << s) - 1)
    res = np.float32(man) / np.float32(1 << 23)
    if new_e >= 0:
        for _ in range(new_e):
            res = np.float32(res * np.float32(2.0))
    else:
        for _ in range(-new_e):
            res = np.float32(res / np.float32(2.0))
    if old_num & (1 << 31):
        res = -res
    return float(res)


def cast_oracle(x: float, exp_bits: int, man_bits: int) -> float:
    """Scalar NumPy transliteration of float_kernel.cu:10-92, used as the
    correctness oracle in tests.  Follows the CUDA control flow literally."""
    _validate(exp_bits, man_bits)
    f = np.float32(x)
    old_num = int(np.array(f, np.float32).view(np.uint32))
    exp = (old_num & 0x7F800000) >> 23
    man = old_num & 0x007FFFFF
    true_exp = exp - 127
    if exp == 0xFF or (exp == 0x00 and man == 0):
        return float(f)
    if exp > 0:
        man = man | (1 << 23)
        diy_bias = (1 << (exp_bits - 1)) - 1
        new_e = true_exp + diy_bias
        if new_e >= (1 << exp_bits) - 1:
            return float(np.inf if f > 0 else -np.inf)
        s = 23 - man_bits
        if new_e > 0:
            if man_bits == 23 or (man & (1 << (s - 1))) == 0:
                man = man & ~((1 << s) - 1)
            elif (man & ((1 << (s - 1)) - 1)) != 0:
                man = (man + (1 << (s - 1))) & ~((1 << s) - 1)
            else:
                if (man & (1 << s)) != 0:
                    man = man + (1 << (s - 1))
                man = man & ~((1 << s) - 1)
            new_e -= diy_bias
        else:
            shift_amt = 1 - new_e
            man = man >> shift_amt if shift_amt < 32 else 0
            new_e = 1 - diy_bias
            if man_bits == 23:  # deviation 1: defined as no rounding
                pass
            elif (man & (1 << (s - 1))) == 0:
                man = man & ~((1 << s) - 1)
            elif (man & ((1 << (s - 1)) - 1)) != 0:
                man = (man + (1 << (s - 1))) & ~((1 << s) - 1)
            else:
                if (man & (1 << s)) != 0:
                    man = man + (1 << (s - 1))
                man = man & ~((1 << s) - 1)
        res = np.float32(man) / np.float32(1 << 23)
        if new_e >= 0:
            for _ in range(new_e):
                res = np.float32(res * np.float32(2.0))
        else:
            for _ in range(-new_e):
                res = np.float32(res / np.float32(2.0))
        if old_num & (1 << 31):
            res = -res
        return float(res)
    return 0.0
