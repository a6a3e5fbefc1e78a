"""Pallas elementwise eXmY quantize kernel — the native analog of the
reference's CUDA quantize kernel.

Reference: `float_kernel_nearest` launches one CUDA thread per element
(CPDtorch/quant/quant_cuda/float_kernel.cu:94-101, quant.cu:14-25).  The
TPU-native shape of the same op is a VPU kernel over (8,128)-tiled VMEM
blocks: each grid step streams one block HBM->VMEM, applies the bit-exact
cast body (quant/numerics.py `cast_body` — shared with the XLA path, so the
kernel *is* the oracle) and streams it back.  Unlike the CUDA kernel this is
pure: no in-place mutation (quant.cu:22-23's aliasing trap disappears).

XLA already fuses `cast_to_format` into surrounding elementwise work, so the
kernel's value is (a) demonstrating the native path end-to-end, (b) avoiding
fusion-boundary materialization for very large standalone quantize calls,
and (c) being the template the quantized-GEMM kernel builds on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from ..compat import pallas as pl, pallas_tpu as pltpu
from ..obs import scopes

from ..quant.numerics import (_scale_pow2, _validate, _validate_wire,
                              cast_body, cast_body_sr,
                              format_max_exponent, max_finite, pack_code,
                              sidecar_bytes, unpack_code, wire_bytes)

__all__ = ["quantize_pallas", "quantize_pallas_sr", "quantize_add_pallas",
           "quantize_add_pallas_bits", "hop_pack_pallas",
           "quantize_pack_pallas", "digest_rows_pallas",
           "fletcher_mod65521"]

_LANES = 128
_BLOCK_ROWS = 512  # (512, 128) fp32 block = 256 KiB of VMEM in + out
_DIGEST_ROWS = 2048  # (2048, 128) u8 block = 256 KiB (digest kernel)


def _quantize_kernel(x_ref, o_ref, *, exp_bits: int, man_bits: int):
    o_ref[:] = cast_body(x_ref[:], exp_bits, man_bits)


def _quantize_sr_kernel(x_ref, r_ref, o_ref, *, exp_bits: int, man_bits: int):
    o_ref[:] = cast_body_sr(x_ref[:], exp_bits, man_bits, r_ref[:])


def _to_blocks(x: jnp.ndarray):
    """Flatten + zero-pad an array to (grid*_BLOCK_ROWS, _LANES) tiles."""
    n = x.size
    rows = -(-n // _LANES)
    pad = rows * _LANES - n
    flat = jnp.pad(x.reshape(-1), (0, pad))
    grid = -(-rows // _BLOCK_ROWS)
    padded_rows = grid * _BLOCK_ROWS
    flat = jnp.pad(flat.reshape(rows, _LANES),
                   ((0, padded_rows - rows), (0, 0)))
    return flat, grid, padded_rows


def _block_spec():
    return pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def quantize_pallas(x: jnp.ndarray, exp_bits: int, man_bits: int,
                    interpret: bool = False) -> jnp.ndarray:
    """eXmY cast of an arbitrary-shape fp32 array via a Pallas TPU kernel.

    Bit-identical to `cast_to_format` (same body).  `interpret=True` runs
    the kernel in the Pallas interpreter for CPU testing."""
    _validate(exp_bits, man_bits)
    x = jnp.asarray(x, jnp.float32)
    shape = x.shape
    n = x.size
    if n == 0:
        return x
    flat, grid, padded_rows = _to_blocks(x)

    call = pl.pallas_call(
        functools.partial(_quantize_kernel, exp_bits=exp_bits,
                          man_bits=man_bits),
        out_shape=jax.ShapeDtypeStruct((padded_rows, _LANES), jnp.float32),
        grid=(grid,),
        in_specs=[_block_spec()],
        out_specs=_block_spec(),
        interpret=interpret,
        name=scopes.kernel_name(scopes.KERNEL_QUANTIZE),
    )
    with jax.named_scope(scopes.KERNEL_QUANTIZE):
        out = call(flat)
    return out.reshape(-1)[:n].reshape(shape)


def _quantize_add_kernel(x_ref, y_ref, o_ref, *, exp_bits: int,
                         man_bits: int):
    o_ref[:] = cast_body(x_ref[:] + y_ref[:], exp_bits, man_bits)


def _quantize_add_sr_kernel(x_ref, y_ref, r_ref, o_ref, *, exp_bits: int,
                            man_bits: int):
    o_ref[:] = cast_body_sr(x_ref[:] + y_ref[:], exp_bits, man_bits,
                            r_ref[:])


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def quantize_add_pallas(x: jnp.ndarray, y: jnp.ndarray, exp_bits: int,
                        man_bits: int,
                        interpret: bool = False) -> jnp.ndarray:
    """Fused quantize-accumulate: ``cast(x + y)`` in ONE VPU kernel — the
    per-hop body of the ring reduce-scatter (parallel/ring.py), where the
    add and the cast would otherwise be separate HBM round-trips per hop.
    Bit-identical to ``cast_to_format(x + y)`` (same `cast_body`)."""
    _validate(exp_bits, man_bits)
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    if x.shape != y.shape:
        raise ValueError(f"shape mismatch {x.shape} vs {y.shape}")
    shape, n = x.shape, x.size
    if n == 0:
        return x
    xf, grid, padded_rows = _to_blocks(x)
    yf, _, _ = _to_blocks(y)
    call = pl.pallas_call(
        functools.partial(_quantize_add_kernel, exp_bits=exp_bits,
                          man_bits=man_bits),
        out_shape=jax.ShapeDtypeStruct((padded_rows, _LANES), jnp.float32),
        grid=(grid,),
        in_specs=[_block_spec(), _block_spec()],
        out_specs=_block_spec(),
        interpret=interpret,
        name=scopes.kernel_name(scopes.KERNEL_QUANTIZE_ADD),
    )
    with jax.named_scope(scopes.KERNEL_QUANTIZE_ADD):
        out = call(xf, yf)
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnums=(2, 3, 5))
def quantize_add_pallas_bits(x: jnp.ndarray, y: jnp.ndarray, exp_bits: int,
                             man_bits: int, rbits: jnp.ndarray,
                             interpret: bool = False) -> jnp.ndarray:
    """Stochastic-rounding fused quantize-accumulate: ``cast_sr(x + y)``
    with EXPLICIT uint32 round bits streamed in as an operand (the ring
    hop passes offset-indexed `sr_bits_at` bits, so the kernel stays
    bit-identical to the XLA path and transport-invariant).  Bit-identical
    to ``cast_body_sr(x + y, ..., rbits)``."""
    _validate(exp_bits, man_bits)
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    if x.shape != y.shape:
        raise ValueError(f"shape mismatch {x.shape} vs {y.shape}")
    shape, n = x.shape, x.size
    if n == 0:
        return x
    rbits = jnp.broadcast_to(jnp.asarray(rbits, jnp.uint32), shape)
    xf, grid, padded_rows = _to_blocks(x)
    yf, _, _ = _to_blocks(y)
    rf, _, _ = _to_blocks(rbits)
    call = pl.pallas_call(
        functools.partial(_quantize_add_sr_kernel, exp_bits=exp_bits,
                          man_bits=man_bits),
        out_shape=jax.ShapeDtypeStruct((padded_rows, _LANES), jnp.float32),
        grid=(grid,),
        in_specs=[_block_spec(), _block_spec(), _block_spec()],
        out_specs=_block_spec(),
        interpret=interpret,
        name=scopes.kernel_name(scopes.KERNEL_QUANTIZE_ADD_SR),
    )
    with jax.named_scope(scopes.KERNEL_QUANTIZE_ADD_SR):
        out = call(xf, yf, rf)
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnums=(1, 2, 4))
def quantize_pallas_sr(x: jnp.ndarray, exp_bits: int, man_bits: int,
                       key: jax.Array, interpret: bool = False) -> jnp.ndarray:
    """Stochastically-rounded eXmY cast via a Pallas TPU kernel.

    Random bits are generated with the host-side JAX PRNG and streamed into
    the kernel as a second operand (rather than seeding an on-chip PRNG), so
    this is bit-identical to `cast_to_format_sr(x, exp, man, key)` — the
    kernel and the XLA path consume the SAME bitstream and tests can assert
    exact equality between them."""
    _validate(exp_bits, man_bits)
    x = jnp.asarray(x, jnp.float32)
    shape = x.shape
    n = x.size
    if n == 0:
        return x
    rbits = jax.random.bits(key, shape, jnp.uint32)
    flat, grid, padded_rows = _to_blocks(x)
    rflat, _, _ = _to_blocks(rbits)

    call = pl.pallas_call(
        functools.partial(_quantize_sr_kernel, exp_bits=exp_bits,
                          man_bits=man_bits),
        out_shape=jax.ShapeDtypeStruct((padded_rows, _LANES), jnp.float32),
        grid=(grid,),
        in_specs=[_block_spec(), _block_spec()],
        out_specs=_block_spec(),
        interpret=interpret,
        name=scopes.kernel_name(scopes.KERNEL_QUANTIZE_SR),
    )
    with jax.named_scope(scopes.KERNEL_QUANTIZE_SR):
        out = call(flat, rflat)
    return out.reshape(-1)[:n].reshape(shape)


# ---------------------------------------------------------------------------
# Fused wire kernels (ISSUE 9): the ENTIRE per-hop ring wire path —
# unpack the received code words, accumulate the local contribution,
# (block-)scale, quantize, re-pack, and Fletcher-digest BOTH wire
# buffers — in ONE Pallas kernel.
#
# Why: the self-verifying transport used to run its digests as a
# separate XLA pass over the packed words (docs/PERF.md measured it at
# +449-566% of the clean reduce), and pack/unpack themselves were
# separate HBM round-trips around the quantize-accumulate kernel.  Here
# one kernel streams the received bytes and the local gradients through
# VMEM once and emits the new partial (fp32), the new code words, and
# the (s1, s2) Fletcher partial sums of both buffers — so `verify=True`
# costs a few VPU ops per element instead of extra passes.
#
# Bitwise contract: every stage reuses the SAME un-jitted bodies as the
# XLA path (`cast_body`/`cast_body_sr`, `pack_code`/`unpack_code`), and
# the in-kernel mod-65521 arithmetic (`fletcher_mod65521` — shift/add
# only, no integer division, Mosaic-safe) is exact, so the kernel's
# digest word equals `integrity.wire_digest` on the same buffer and the
# kernel's partial equals the XLA hop bit-for-bit (gated in
# tests/test_ops_pallas.py and CI's reduce-smoke).
#
# Block-scaled hops (`block_size=`) are fused when the block is a
# multiple of 128 lanes dividing the 64k-element kernel tile (the
# default 128 qualifies): blocks are then whole kernel rows, so the
# per-block max is a row reduction.  The 1-byte-per-block shift sidecar
# is assembled (and its few bytes digested) in XLA and combined with
# the kernel's code-lane digest via `integrity.digest_concat`.
# ---------------------------------------------------------------------------

_DIGEST_MOD = 65521  # == integrity.DIGEST_MOD (import-leaf; pinned in
#                      tests/test_integrity.py)


def fletcher_mod65521(x: jnp.ndarray) -> jnp.ndarray:
    """x % 65521 for uint32 inputs using only shifts/masks/adds
    (2^16 ≡ 15 mod 65521), exact for the full uint32 range — the
    Mosaic-safe modulus of the in-kernel Fletcher digest.  Pinned
    against `%` in tests."""
    f = jnp.uint32(15)
    x = (x & jnp.uint32(0xFFFF)) + (x >> 16) * f      # < 2^20
    x = (x & jnp.uint32(0xFFFF)) + (x >> 16) * f      # < 65761
    m = jnp.uint32(_DIGEST_MOD)
    return jnp.where(x >= m, x - m, x)


def _usum(x: jnp.ndarray, axis=None, keepdims: bool = False) -> jnp.ndarray:
    """Sum of uint32 values whose total stays below 2^31 (every caller's
    overflow audit), taken as int32: Mosaic on libtpu 0.0.34 refuses
    "Reductions over unsigned integers", and below 2^31 the two sums are
    the same number.  Same-width casts, not bitcasts: the full reduction
    is a scalar, which `tpu.bitcast` does not take."""
    return jnp.sum(x.astype(jnp.int32), axis=axis,
                   keepdims=keepdims).astype(jnp.uint32)


def _tile_fletcher(bytes_u32: jnp.ndarray, byte_pos: jnp.ndarray) -> tuple:
    """Partial Fletcher sums (s1, s2) of one (R, 128) tile of byte
    values at absolute byte positions `byte_pos` (uint32).  Zero pad
    bytes contribute nothing, so no masking is needed.  Overflow-safe:
    Σ bytes <= 65536·255 < 2^24; per-lane products < 2^8·2^16 = 2^24,
    row sums of 128 < 2^31, mod'd row partials sum < 512·2^16."""
    s1 = fletcher_mod65521(_usum(bytes_u32))
    posm = fletcher_mod65521(byte_pos) + jnp.uint32(1)
    rows = fletcher_mod65521(_usum(bytes_u32 * posm, axis=1, keepdims=True))
    s2 = fletcher_mod65521(_usum(rows))
    return s1, s2


def _exp_field(x: jnp.ndarray) -> jnp.ndarray:
    return ((jax.lax.bitcast_convert_type(x, jnp.uint32) >> 23)
            & jnp.uint32(0xFF)).astype(jnp.int32)


def _flush_low_kernel(x: jnp.ndarray) -> jnp.ndarray:
    low = _exp_field(x) == 0
    return jnp.where(low, jnp.float32(0.0), x)


def _make_wire_kernel(exp_bits: int, man_bits: int, wb: int, *,
                      first: bool, sr: bool, blocked, want_digest: bool):
    """Build the fused hop kernel body.  Ref order: [wb in-planes +
    k_in plane (mid-hop only)], g, [rbits], then outputs: res, wb
    out-planes, [k_out plane (blocked)], [digest (1, 4) SMEM]."""
    emax = format_max_exponent(exp_bits)
    mf = float(max_finite(exp_bits, man_bits))

    def kernel(*refs):
        i = 0
        in_planes = k_in_ref = None
        if not first:
            in_planes = refs[:wb]
            i = wb
            if blocked is not None:
                k_in_ref = refs[i]
                i += 1
        g_ref = refs[i]
        i += 1
        r_ref = None
        if sr:
            r_ref = refs[i]
            i += 1
        res_ref = refs[i]
        i += 1
        out_planes = refs[i:i + wb]
        i += wb
        k_out_ref = None
        if blocked is not None:
            k_out_ref = refs[i]
            i += 1
        dig_ref = refs[i] if want_digest else None

        # -- unpack + accumulate ----------------------------------------
        code_in = None
        if first:
            s = g_ref[:]
        else:
            code_in = in_planes[0][:].astype(jnp.uint32)
            for k in range(1, wb):
                code_in = code_in | (in_planes[k][:].astype(jnp.uint32)
                                     << (8 * k))
            prev = unpack_code(code_in, exp_bits, man_bits)
            if blocked is not None:
                k_in = k_in_ref[:]
                flush = (jnp.isfinite(prev) & (prev != 0)
                         & (_exp_field(prev) - 127 + k_in <= -127))
                prev = _flush_low_kernel(
                    jnp.where(flush, jnp.float32(0.0),
                              _scale_pow2(prev, k_in)))
            s = prev + g_ref[:]

        # -- (block-)scale + quantize -----------------------------------
        if blocked is None:
            q = (cast_body_sr(s, exp_bits, man_bits, r_ref[:]) if sr
                 else cast_body(s, exp_bits, man_bits))
            res_ref[:] = q
        else:
            rows, lanes = s.shape
            c = blocked // lanes           # rows per block (>= 1)
            s = _flush_low_kernel(s)
            mag = jnp.where(jnp.isfinite(s), jnp.abs(s), 0.0)
            rmax = jnp.max(mag, axis=1, keepdims=True)      # (rows, 1)
            if c > 1:
                gmax = jnp.max(rmax.reshape(rows // c, c), axis=1,
                               keepdims=True)
                rmax = jnp.broadcast_to(gmax, (rows // c, c)).reshape(
                    rows, 1)
            bmax = jnp.broadcast_to(rmax, (rows, lanes))
            k_blk = jnp.where(bmax > 0, _exp_field(bmax) - 127 - emax, 0)
            k_blk = jnp.clip(k_blk, -128, 127)
            tiny = (jnp.isfinite(s) & (s != 0)
                    & (_exp_field(s) - 127 - k_blk <= -127))
            s = jnp.where(tiny, jnp.float32(0.0), s)
            y = _scale_pow2(s, -k_blk)
            q = (cast_body_sr(y, exp_bits, man_bits, r_ref[:]) if sr
                 else cast_body(y, exp_bits, man_bits))
            carry = jnp.isfinite(y) & (jnp.abs(q) > jnp.float32(mf))
            q = jnp.where(carry,
                          jnp.where(q > 0, jnp.float32(mf),
                                    jnp.float32(-mf)), q)
            out_flush = (jnp.isfinite(q) & (q != 0)
                         & (_exp_field(q) - 127 + k_blk <= -127))
            res_ref[:] = _flush_low_kernel(
                jnp.where(out_flush, jnp.float32(0.0),
                          _scale_pow2(q, k_blk)))
            k_out_ref[:] = k_blk
            # canonicalize the wire: values the unscale flushes (and
            # ±0.0) encode as code 0, exactly what the XLA path's
            # re-pack of the flushed partial emits — the two paths'
            # wire BYTES, not just their decoded values, must agree
            q = jnp.where(out_flush | (q == 0), jnp.float32(0.0), q)

        # -- pack + digest ----------------------------------------------
        code = pack_code(q, exp_bits, man_bits)
        for k in range(wb):
            out_planes[k][:] = ((code >> (8 * k))
                                & jnp.uint32(0xFF)).astype(jnp.uint8)
        if want_digest:
            pid = pl.program_id(0)
            rows, lanes = res_ref.shape
            elem = (jnp.uint32(rows * lanes) * pid.astype(jnp.uint32)
                    + lax.broadcasted_iota(jnp.uint32, (rows, lanes), 0)
                    * jnp.uint32(lanes)
                    + lax.broadcasted_iota(jnp.uint32, (rows, lanes), 1))

            def plane_sums(code_words):
                s1 = jnp.uint32(0)
                s2 = jnp.uint32(0)
                for k in range(wb):
                    b = (code_words >> (8 * k)) & jnp.uint32(0xFF)
                    p1, p2 = _tile_fletcher(
                        b, elem * jnp.uint32(wb) + jnp.uint32(k))
                    s1 = fletcher_mod65521(s1 + p1)
                    s2 = fletcher_mod65521(s2 + p2)
                return s1, s2

            o1, o2 = plane_sums(code)
            i1 = i2 = jnp.uint32(0)
            if not first:
                i1, i2 = plane_sums(code_in)

            @pl.when(pid == 0)
            def _():
                for j in range(4):
                    dig_ref[0, j] = jnp.uint32(0)

            for j, v in enumerate((i1, i2, o1, o2)):
                dig_ref[0, j] = fletcher_mod65521(dig_ref[0, j] + v)

    return kernel


def _assemble_wire(planes, n: int, wb: int) -> jnp.ndarray:
    """Byte planes back to the (n, wb) uint8 wire layout of pack_exmy."""
    return jnp.stack([p.reshape(-1)[:n] for p in planes], axis=-1)


def _wire_call(codes_in, k_in, sidecar_in, g, exp_bits, man_bits, rbits,
               block_size, want_digest, interpret):
    """Shared pallas_call assembly for the first-hop and mid-hop fused
    wire kernels.  Returns (res (n,), wire, [digest_in, digest_out]) —
    the wire in EXACTLY the layout the XLA path ships (``(n, wb)`` code
    words, or the flat blocked buffer with its sidecar lane), and the
    digests bitwise equal to `integrity.wire_digest` of those buffers."""
    _validate_wire(exp_bits, man_bits)
    wb = wire_bytes(exp_bits, man_bits)
    n = g.size
    first = codes_in is None
    sr = rbits is not None
    blocked = block_size is not None
    if blocked and (block_size % _LANES != 0
                    or (_BLOCK_ROWS * _LANES) % block_size != 0):
        raise ValueError(
            f"fused blocked hop needs block_size a multiple of {_LANES} "
            f"dividing {_BLOCK_ROWS * _LANES}, got {block_size} — the "
            f"XLA path (parallel/ring.py) handles other sizes")
    g = jnp.asarray(g, jnp.float32).reshape(-1)
    gf, grid, padded_rows = _to_blocks(g)
    operands = []
    in_specs = []
    if not first:
        for k in range(wb):
            pf, _, _ = _to_blocks(codes_in[:, k])
            operands.append(pf)
            in_specs.append(_block_spec())
        if blocked:
            kf, _, _ = _to_blocks(k_in.astype(jnp.int32))
            operands.append(kf)
            in_specs.append(_block_spec())
    operands.append(gf)
    in_specs.append(_block_spec())
    if sr:
        rf, _, _ = _to_blocks(jnp.asarray(rbits, jnp.uint32))
        operands.append(rf)
        in_specs.append(_block_spec())

    out_shape = [jax.ShapeDtypeStruct((padded_rows, _LANES), jnp.float32)]
    out_specs = [_block_spec()]
    for _ in range(wb):
        out_shape.append(jax.ShapeDtypeStruct((padded_rows, _LANES),
                                              jnp.uint8))
        out_specs.append(_block_spec())
    if blocked:
        out_shape.append(jax.ShapeDtypeStruct((padded_rows, _LANES),
                                              jnp.int32))
        out_specs.append(_block_spec())
    if want_digest:
        out_shape.append(jax.ShapeDtypeStruct((1, 4), jnp.uint32))
        # 4 running digest scalars in SMEM — the lane-multiple tiling
        # rule is about VMEM vector blocks; SMEM is word-addressed
        out_specs.append(pl.BlockSpec(  # cpd: disable=pallas-hygiene
            (1, 4), lambda i: (0, 0), memory_space=pltpu.SMEM))

    kernel = _make_wire_kernel(exp_bits, man_bits, wb, first=first,
                               sr=sr, blocked=block_size if blocked
                               else None, want_digest=want_digest)
    call = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        interpret=interpret,
        name=scopes.kernel_name(scopes.KERNEL_WIRE_HOP),
    )
    with jax.named_scope(scopes.KERNEL_WIRE_HOP):
        outs = call(*operands)

    res = outs[0].reshape(-1)[:n]
    planes = outs[1:1 + wb]
    idx = 1 + wb
    if not blocked:
        wire = _assemble_wire(planes, n, wb)
        if not want_digest:
            return res, wire
        dig = outs[idx]
        d_out = (dig[0, 3] << 16) | dig[0, 2]
        d_in = (dig[0, 1] << 16) | dig[0, 0]
        return res, wire, d_in, d_out

    # blocked: append the sidecar lane, combine its digest contribution
    from ..parallel.integrity import digest_concat, wire_digest
    k_plane = outs[idx]
    idx += 1
    nb = sidecar_bytes(n, block_size)
    rows_per_block = block_size // _LANES
    # block b's shift sits in rows [b*rpb, (b+1)*rpb), any lane
    k_rows = k_plane[:, 0]                      # (padded_rows,)
    shifts = k_rows[::rows_per_block][:nb]
    sidecar = (shifts + 128).astype(jnp.uint8)
    codes_flat = _assemble_wire(planes, n, wb).reshape(-1)
    wire = jnp.concatenate([codes_flat, sidecar])
    if not want_digest:
        return res, wire
    dig = outs[idx]
    d_out_codes = (dig[0, 3] << 16) | dig[0, 2]
    d_out = digest_concat(d_out_codes, n * wb, wire_digest(sidecar))
    d_in_codes = (dig[0, 1] << 16) | dig[0, 0]
    d_in = (digest_concat(d_in_codes, n * wb, wire_digest(sidecar_in))
            if not first else jnp.uint32(0))
    return res, wire, d_in, d_out


def hop_pack_pallas(wire_in: jnp.ndarray, g: jnp.ndarray, exp_bits: int,
                    man_bits: int, *, rbits=None,
                    block_size=None, want_digest: bool = False,
                    interpret: bool = False):
    """One fused ring hop over the packed wire: unpack `wire_in`, add
    the local contribution `g`, (block-)quantize, re-pack, and (with
    ``want_digest``) Fletcher-digest both wire buffers — a single
    Pallas kernel pass (module block comment).

    Returns ``(res, wire_out)`` or ``(res, wire_out, digest_in,
    digest_out)``; `res` is the fp32 partial (bitwise the XLA hop's),
    `wire_out` the exact byte layout `ring_quantized_sum`'s to_wire
    ships, and the digests equal `integrity.wire_digest` of the full
    received/emitted buffers (sidecar lane included)."""
    n = g.size
    if block_size is None:
        codes_in = wire_in.reshape(n, wire_bytes(exp_bits, man_bits))
        k_in = sidecar_in = None
    else:
        wb = wire_bytes(exp_bits, man_bits)
        nb = sidecar_bytes(n, block_size)
        codes_in = wire_in[:n * wb].reshape(n, wb)
        sidecar_in = wire_in[n * wb:n * wb + nb]
        k_in = jnp.repeat(sidecar_in.astype(jnp.int32) - 128,
                          block_size)[:n]
    return _wire_call(codes_in, k_in, sidecar_in, g, exp_bits, man_bits,
                      rbits, block_size, want_digest, interpret)


def _digest_rows_kernel(b_ref, o_ref, *, w: int, sub_per_row: int):
    """One grid step digests tile ``j`` of EVERY row at once: the block
    stacks, for each of the ``w`` rows, ``sub_per_row`` sublanes of its
    j-th tile — per-row Fletcher partials come out of masked reductions
    over the sublane axis, so a whole W-row gather wire costs T grid
    steps (not W·T; one step for the common one-tile case, which is
    what keeps the interpret-mode CPU emulation honest).

    Overflow audit (uint32): per-sublane byte sums <= 128·255 < 2^15;
    per-sublane weighted sums: byte·(pos mod 65521 + 1) < 2^24, 128
    lanes -> < 2^31, mod'd immediately; masked per-row sums over
    sub_per_row <= 2048 sublanes of values < 65521 -> < 2^27."""
    j = pl.program_id(0)
    bytes_u32 = b_ref[:].astype(jnp.uint32)
    rows, lanes = b_ref.shape                  # rows = w * sub_per_row
    # sub_per_row is a power of two (digest_rows_pallas), so the sublane
    # within its row and the row id are a mask and a shift — Mosaic has
    # no vector integer division
    shift = sub_per_row.bit_length() - 1
    idx0 = lax.broadcasted_iota(jnp.uint32, (rows, lanes), 0)
    sub = idx0 & jnp.uint32(sub_per_row - 1)
    pos = (j.astype(jnp.uint32)
           * jnp.uint32(sub_per_row * lanes)
           + sub * jnp.uint32(lanes)
           + lax.broadcasted_iota(jnp.uint32, (rows, lanes), 1))
    posm = fletcher_mod65521(pos) + jnp.uint32(1)
    c1 = _usum(bytes_u32, axis=1, keepdims=True)           # (rows, 1)
    c2 = fletcher_mod65521(_usum(bytes_u32 * posm, axis=1, keepdims=True))
    row_id = lax.broadcasted_iota(jnp.uint32, (rows, 1), 0) >> shift

    @pl.when(j == 0)
    def _():
        for r in range(w):
            o_ref[r, 0] = jnp.uint32(0)
            o_ref[r, 1] = jnp.uint32(0)

    zero = jnp.uint32(0)
    for r in range(w):
        m = row_id == jnp.uint32(r)
        p1 = fletcher_mod65521(_usum(jnp.where(m, c1, zero)))
        p2 = fletcher_mod65521(_usum(jnp.where(m, c2, zero)))
        o_ref[r, 0] = fletcher_mod65521(o_ref[r, 0] + p1)
        o_ref[r, 1] = fletcher_mod65521(o_ref[r, 1] + p2)


@functools.partial(jax.jit, static_argnums=(1,))
def digest_rows_pallas(rows: jnp.ndarray,
                       interpret: bool = False) -> jnp.ndarray:
    """Per-row Fletcher digest of a (W, n_bytes) uint8 buffer in ONE
    Pallas pass — bitwise equal to ``jax.vmap(integrity.wire_digest)``
    over the rows (pinned in tests/test_ops_pallas.py).

    This is the LAST fused digest of ISSUE 12 leg 4: the verified ring's
    all-gather row check used to hash the received rows XLA-side
    (`wire_digest` per row) — the one wire digest left outside the pack
    kernels.  With this kernel the fused verified arm emits every hop
    digest from `hop_pack_pallas` and every gather-row digest from here,
    so no XLA-side wire digest remains on that arm.  Zero pad bytes
    contribute nothing to either Fletcher sum, so rows pad freely to
    the tile grid."""
    rows = jnp.asarray(rows, jnp.uint8)
    if rows.ndim != 2:
        raise ValueError(f"digest_rows_pallas wants (W, n_bytes) uint8, "
                         f"got shape {rows.shape}")
    w, nb = rows.shape
    if nb == 0 or w == 0:
        return jnp.zeros((w,), jnp.uint32)
    # sublanes of one row per grid step: cap the whole block (all W
    # rows' tiles) near 2 MiB of VMEM, and cap per-row sublanes at 2048
    # (the masked-sum overflow bound above), rounded down to a power of
    # two so the kernel finds a sublane's row with a shift
    sub_per_row = 1 << (max(1, min(2048, 16384 // w)).bit_length() - 1)
    tile = sub_per_row * _LANES
    t = -(-nb // tile)
    padded = jnp.pad(rows, ((0, 0), (0, t * tile - nb)))
    # (w, t, sub, 128) -> (t, w·sub, 128): tile j of every row is one
    # contiguous block the grid walks in j order
    stacked = (padded.reshape(w, t, sub_per_row, _LANES)
               .transpose(1, 0, 2, 3)
               .reshape(t * w * sub_per_row, _LANES))

    # 2 running digest scalars per row in SMEM — the lane-multiple
    # tiling rule is about VMEM vector blocks; SMEM is word-addressed
    dig_spec = pl.BlockSpec(  # cpd: disable=pallas-hygiene
        (w, 2), lambda j: (0, 0), memory_space=pltpu.SMEM)
    call = pl.pallas_call(
        functools.partial(_digest_rows_kernel, w=w,
                          sub_per_row=sub_per_row),
        out_shape=jax.ShapeDtypeStruct((w, 2), jnp.uint32),
        grid=(t,),
        in_specs=[pl.BlockSpec((w * sub_per_row, _LANES),
                               lambda j: (j, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=dig_spec,
        interpret=interpret,
        name=scopes.kernel_name(scopes.KERNEL_DIGEST_ROWS),
    )
    with jax.named_scope(scopes.KERNEL_DIGEST_ROWS):
        out = call(stacked)
    return (out[:, 1] << 16) | out[:, 0]


def quantize_pack_pallas(g: jnp.ndarray, exp_bits: int, man_bits: int, *,
                         rbits=None, block_size=None,
                         want_digest: bool = False,
                         interpret: bool = False):
    """The ring's hop-0 wire emit, fused: (block-)quantize the local
    chunk and pack it (plus digest) in one kernel — `hop_pack_pallas`
    without a received wire.  Returns ``(res, wire)`` or ``(res, wire,
    digest)``."""
    out = _wire_call(None, None, None, g, exp_bits, man_bits, rbits,
                     block_size, want_digest, interpret)
    if want_digest:
        res, wire, _, d_out = out
        return res, wire, d_out
    return out
