"""Ring-transport quantized all-reduce: reduce-scatter + all-gather rings
moving bit-packed eXmY payloads (EQuARX-style, PAPERS.md).

The faithful gather path (parallel/dist.py) ships every rank's FULL
gradient to every rank — (W-1)·n raw fp32 elements per device on the wire
and a (W, n) gathered stack resident — before the ordered requantizing
scan even starts.  The ring transport does the same class of ordered
quantized reduction while moving ~2·n·(W-1)/W elements per device (2/W of
the gather path's element count) at ``wire_bytes(exp, man)`` bytes each
(quant/numerics.pack_exmy), with O(n/W) peak transient memory: partial
sums — which are post-quantize and therefore always in the format's value
set, APS or not — are what rides the wire, never raw fp32.

Transport semantics (the documented per-chunk rank rotation)
-----------------------------------------------------------

The flat buffer is zero-padded to W·chunk and split into W chunks; device
d finishes owning chunk d.  Chunk c's partial starts on device (c+1) mod W
as ``q(0 + g_{c+1}[c])`` and hops rightward, each hop folding in the host
device's local contribution:

    hop t (t = 0..W-1): device (c+1+t) mod W applies
        res = q(res + g_{(c+1+t) mod W}[c])            (plain; sites 0)
        y = q(g - comp); tmp = q(res + y);              (Kahan; sites 0-3)
        comp = q(q(tmp - res) - y); res = tmp

so chunk c accumulates ranks in the ROTATED order (c+1, c+2, ..., c) mod
W — each chunk's order is a rotation of rank order, not rank order
itself.  A single unidirectional ring cannot give every chunk the
identical start rank while keeping all devices busy, so the rotation IS
the transport's semantics: deterministic, topology-independent, and
emulated bit-for-bit by the single-device `ring_oracle_sum` (the
correctness gate — tests assert bitwise equality distributed-vs-oracle
across formats, world sizes and rounding modes).  Versus the gather
path's single global rank order the result differs only by that
per-chunk rotation of the same ordered requantized sum; both are equally
faithful "some fixed documented order" reductions (the property psum
cannot give), and tests pin their statistical agreement.

Stochastic rounding composes transport-invariantly: per-element bits are
indexed by (key, hop step t, cast site, GLOBAL flat offset) — the same
(key, step, site, offset) scheme as reduction.py — so the oracle, the
distributed ring, and any resharding of the ring draw identical bits.

Kahan on a ring: the compensation term must ride along with the partial
(the next hop's casts need it), so the reduce-scatter phase ships 2
values per element; the all-gather phase ships only the result.  Still
~(W-1)·3/W elements per device vs the gather path's (W-1)·n.

The per-hop body is one fused quantize-accumulate kernel on TPU
(ops/quantize.quantize_add_pallas, sharing `cast_body` with everything
else); elsewhere the XLA composition of the same ops (bit-identical —
same body).

Wire integrity (ISSUE 4)
------------------------

``verify=True`` turns on the self-verifying transport: every hop
payload rides a tagged Fletcher checksum (parallel/integrity.hop_tag —
digest ^ hop-index ^ sender-rank, so flipped bits, dropped payloads AND
coherent stale self-echoes all fail at the receiving hop), the final
all-gather rows are tag-checked the same way, and each rank's WHOLE
gathered wire digest — composed from the per-row digests it just
computed, via `integrity.digest_concat` (the reconstructed vector is a
deterministic function of those bytes, so wire agreement IS vector
agreement, without a second full-vector hash pass) — is pmin/pmax-
agreed across replicas.  On the fused wire path the per-hop digests
come out of the pack kernel itself (ops/quantize.hop_pack_pallas) —
verification is not a separate pass over the wire words.  The function
returns ``(vec, report)`` with replicated int32 scalars ``hop_bad`` /
``gather_bad`` (psum'd mismatch counts), ``agree`` and ``ok``.  The
scan-site checksums matter because a corrupted partial keeps hopping
and lands the SAME wrong sum on every replica — invisible to any
cross-replica comparison; the agreement digest matters because a
gather-site corruption diverges one replica — invisible to the hops it
never rode.

``fault=(code, rank)`` injects the matching deterministic wire faults
(resilience/inject.WIRE_KINDS: 1=flip one bit, 2=stale self-echo,
3=drop) into the first reduce-scatter hop AND the all-gather wire on
that rank — the attack exists independently of the defense, so a run
with ``verify=False`` silently computes a wrong (or divergent) sum,
which is exactly the EQuARX failure mode the checksums exist to catch.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs import scopes
from ..quant.numerics import (cast_body_blocked, cast_to_format,
                              cast_to_format_sr_at, pack_exmy,
                              pack_exmy_blocked, sr_bits_at,
                              unpack_exmy, unpack_exmy_blocked, wire_bytes,
                              wire_bytes_blocked)

__all__ = ["ring_quantized_sum", "ring_oracle_sum", "ring_transport_bytes",
           "gather_transport_bytes", "transport_table", "pad_to_world",
           "reflatten_to_world", "ring_chunk_size", "hierarchical_ring_sum",
           "ring_oracle_sum_multi"]


def ring_chunk_size(n: int, world: int) -> int:
    """Elements per ring chunk: ceil(n / world) — one chunk per device.
    The same quantum parallel/zero.py shards its flat layouts by."""
    return math.ceil(n / world) if n else 0


def pad_to_world(flat: jnp.ndarray, world: int) -> jnp.ndarray:
    """Zero-pad a flat (n,) vector to world * ring_chunk_size(n, world).
    Exact zeros are rounding-invariant, so pad elements never perturb a
    quantized reduction (and are sliced off before returning)."""
    n = flat.shape[0]
    return jnp.pad(flat, (0, world * ring_chunk_size(n, world) - n))


def reflatten_to_world(flat: jnp.ndarray, total: int,
                       world: int) -> jnp.ndarray:
    """Re-shard a world-padded flat layout for a DIFFERENT world size:
    trim the old pad (the real data is the first ``total`` elements —
    the invariant every padded flat layout here keeps, because exact-zero
    grads leave exact-zero momentum in the pad) and re-pad through
    `pad_to_world` at the new world.  Bitwise-faithful in both
    directions, for ANY world pair — including non-divisible shrinks
    (8 -> 3): only the pad length changes, never a data element.  The
    runtime half of the elastic-restart contract (ISSUE 4/19): the
    checkpoint layer re-flattens through this on a ``world=`` restore,
    and the elastic shrink/regrow path re-flattens live ZeRO state the
    same way."""
    if total > flat.shape[0]:
        raise ValueError(
            f"reflatten_to_world: flat layout holds {flat.shape[0]} "
            f"elements but total={total} are claimed as data — the "
            f"caller's layout and parameter count disagree")
    return pad_to_world(flat[:total], world)


def _make_hop_q(exp: int, man: int, key, block: Optional[int] = None):
    """Per-hop quantizer ``q(x, step, site, offs)`` with reduction.py's
    exact bit-indexing contract: RTNE when key is None, else SR bits from
    (key, step, site, global offset).  Unlike reduction._make_q the
    offsets are a call argument — on the ring the chunk (hence its global
    offsets) a device is casting changes every hop.

    ``block`` switches every cast site to the block-scaled cast
    (`numerics.cast_body_blocked`, blocks of ``block`` elements along the
    LAST axis): each block of the partial is power-of-2-shifted to the
    format's top exponent before the cast and shifted back after — the
    EQuARX-style wire.  The distributed ring and `ring_oracle_sum` share
    this one factory, so the blocked transport is oracle-gated exactly
    like the per-tensor one."""
    if key is None:
        if block is None:
            return lambda x, step, site, offs: cast_to_format(x, exp, man)
        return lambda x, step, site, offs: cast_body_blocked(
            x, exp, man, block)

    def q(x, step, site, offs):
        k = jax.random.fold_in(jax.random.fold_in(key, step), site)
        if block is None:
            return cast_to_format_sr_at(x, exp, man, k, offs)
        rbits = jnp.broadcast_to(sr_bits_at(k, offs), jnp.shape(x))
        return cast_body_blocked(x, exp, man, block, rbits=rbits)

    return q


def _hop_plain(q, res, g, t, offs, fp32_shortcut):
    """res = q(res + g) — one reduce-scatter hop.  At (8,23) non-Kahan the
    cast is skipped entirely (plain fp32 add), mirroring quantized_sum's
    reference-parity shortcut (dist_util.py:55-59): cast_to_format(8,23)
    would flush fp32-subnormal partials, which the reference's fp32 path
    never does."""
    if fp32_shortcut:
        return res + g
    return q(res + g, t, 0, offs)


def _hop_kahan(q, res, comp, g, t, offs):
    """One Kahan-compensated hop, sites 0-3 exactly as
    reduction.kahan_quantized_sum's scan body."""
    y = q(g - comp, t, 0, offs)
    tmp = q(res + y, t, 1, offs)
    comp = q(q(tmp - res, t, 2, offs) - y, t, 3, offs)
    return tmp, comp


def _flip_first_bit(x: jnp.ndarray) -> jnp.ndarray:
    """The minimal wire corruption: the lowest bit of the first word of
    a payload (uint8 code word or fp32 bit pattern) flipped."""
    flat = jnp.ravel(x)
    if flat.dtype == jnp.uint8:
        flat = flat.at[0].set(flat[0] ^ jnp.uint8(1))
    else:
        b = lax.bitcast_convert_type(flat, jnp.uint32)
        b = b.at[0].set(b[0] ^ jnp.uint32(1))
        flat = lax.bitcast_convert_type(b, x.dtype)
    return flat.reshape(x.shape)


def _apply_hop_fault(recv, sent, code, active):
    """Corrupt a received payload per the wire-fault code when `active`
    (resilience/inject.WIRE_KINDS).  ``stale`` replays this rank's own
    just-sent payload; ``flip`` flips one bit; ``drop`` zeroes.  The
    deferred tag compare (sender-side tag of what was actually sent vs
    receiver-side tag of what actually arrived) catches all three by
    CONTENT: any replay/flip/drop whose bytes differ from the genuine
    payload fails the end-to-end compare, and one whose bytes happen to
    be identical is by definition a no-op on the sum — there is nothing
    to detect."""
    stale = active & (code == 2)
    recv = jnp.where(stale, sent, recv)
    recv = jnp.where(active & (code == 1), _flip_first_bit(recv), recv)
    recv = jnp.where(active & (code == 3), jnp.zeros_like(recv), recv)
    return recv


def _static_world(axis_name, world: Optional[int]) -> int:
    if world is not None:
        return int(world)
    w = lax.psum(1, axis_name)  # concrete int inside shard_map on jax 0.4
    try:
        return int(w)
    except (TypeError, jax.errors.TracerArrayConversionError) as e:
        raise ValueError(
            "ring transport needs the axis size as a static int at trace "
            "time; this JAX returned a traced psum — pass world= "
            "explicitly (e.g. mesh.shape[axis_name])") from e


def ring_quantized_sum(flat: jnp.ndarray, axis_name: str, exp: int, man: int,
                       *, use_kahan: bool = False, key=None,
                       offset_start: int = 0, packed: bool = True,
                       world: Optional[int] = None,
                       fused: Optional[bool] = None,
                       interpret: bool = False,
                       verify: bool = False,
                       fault: Optional[tuple] = None,
                       offsets: Optional[jnp.ndarray] = None,
                       block_scale: bool = False,
                       block_size: int = 128):
    """Ordered quantized SUM of per-rank flat fp32 vectors over `axis_name`
    via a ppermute ring — call inside shard_map.

    Every rank passes its LOCAL (n,) fp32 contribution; every rank returns
    the full (n,) reduced vector (replicated).  Accumulation follows the
    per-chunk rank rotation documented in the module docstring, with every
    partial re-quantized to (exp, man) — `ring_oracle_sum` reproduces the
    result bit-for-bit on one device.

    packed       → ship hop payloads (and the final all-gather) as
                   bit-packed eXmY code words (pack_exmy) instead of fp32.
                   Lossless by construction — partials are post-cast, so
                   they live in the format's value set.  Auto-disabled for
                   formats the codec rejects (man < 2) and a no-op gain at
                   (8, 23) (4-byte code words).
    offset_start → global flat offset of flat[0] in the SR bit-index space
                   (parallel/dist.py's `_leaf_starts` space).
    offsets      → full per-element (n,) uint32 global offsets, for flats
                   that are NOT contiguous in the global space (a bucket
                   spanning non-adjacent leaves — parallel/dist.py's
                   bucketed ring).  Overrides ``offset_start``.  Pad
                   elements are exact zeros, whose cast is rounding-
                   invariant, so their (arbitrary) offsets never matter.
    world        → static axis size; default reads it from the axis.
    fused        → use the fused Pallas quantize-accumulate hop kernel
                   (ops/quantize.quantize_add_pallas; plain path only —
                   Kahan's 4-cast body stays XLA).  Default: TPU backend
                   only.  `interpret` runs that kernel in interpret mode
                   (CPU tests).
    verify       → self-verifying transport (module docstring): returns
                   ``(vec, report)`` with replicated int32 scalars
                   {hop_bad, gather_bad, agree, ok}.  The clean-path
                   result is BITWISE identical to verify=False — the
                   checksums observe the wire, they never touch it.
    fault        → ``(code, rank)`` int32 scalars injecting a
                   deterministic wire fault (inject.WIRE_KINDS; 0 = no
                   fault) into the first reduce-scatter hop and the
                   all-gather wire on that rank.  Applied whether or
                   not `verify` is on — the attack does not need the
                   defense's permission.
    block_scale  → block-scaled wire (EQuARX-style; quant/numerics.py
                   "Block-scaled eXmY codec"): every hop cast shares one
                   power-of-2 scale per ``block_size`` consecutive
                   elements (chunk-local blocks, odd tail included), and
                   the 1-byte-per-block shift sidecar rides the packed
                   wire next to the code words.  Different accumulation
                   NUMERICS than the per-tensor cast — gated by its own
                   extended oracle (`ring_oracle_sum(block_size=...)`),
                   NOT bitwise comparable to block_scale=False.
                   Requires a packable format (man >= 2, not (8, 23)).
    block_size   → elements per shared-scale block (static; default 128
                   — one fp32 cache line's worth per scale byte).
    """
    if isinstance(axis_name, (tuple, list)):
        raise ValueError("ring transport runs over exactly one mesh axis; "
                         f"got {axis_name!r}")
    w = _static_world(axis_name, world)
    n = flat.shape[0]
    flat = jnp.asarray(flat, jnp.float32)
    fp32_shortcut = exp == 8 and man == 23 and not use_kahan
    if block_scale:
        if exp == 8 and man == 23:
            raise ValueError("block_scale=True at (8, 23): the fp32 wire "
                             "has nothing to scale — drop block_scale or "
                             "pick a sub-fp32 format")
        if man < 2:
            raise ValueError(
                f"block_scale=True needs a packable format (man_bits >= 2 "
                f"for the codec's special codes), got ({exp}, {man})")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if not packed:
            raise ValueError("block_scale=True IS the packed sidecar wire; "
                             "packed=False contradicts it")
    if man < 2 or (exp == 8 and man == 23):
        packed = packed and not (man < 2)
        packed = packed and not fp32_shortcut  # 4-byte words: skip the work
    if fused is None:
        fused = jax.default_backend() == "tpu"
    if fused and (use_kahan or fp32_shortcut):
        fused = False
    # the single-kernel wire path (ops/quantize.hop_pack_pallas): packed
    # plain hops, and blocked hops whose blocks are whole kernel rows
    # (a multiple of the 128-lane width dividing the 64k-element tile —
    # the default block_size=128 qualifies); other shapes ride the XLA
    # composition of the same bodies
    fused_wire = (fused and packed and not use_kahan
                  and (not block_scale
                       or (block_size % 128 == 0
                           and 65536 % block_size == 0)))

    padded = pad_to_world(flat, w)
    chunk = padded.shape[0] // w if w else 0
    padded_offs = None
    if offsets is not None:
        if offsets.shape != (n,):
            raise ValueError(f"offsets must be shape ({n},), got "
                             f"{offsets.shape}")
        padded_offs = pad_to_world(offsets.astype(jnp.uint32), w)
    if n == 0:
        if verify:
            i0, i1 = jnp.zeros([], jnp.int32), jnp.ones([], jnp.int32)
            return flat, {"hop_bad": i0, "gather_bad": i0,
                          "agree": i1, "ok": i1}
        return flat
    rank = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % w) for i in range(w)]
    blk = block_size if block_scale else None
    q = _make_hop_q(exp, man, key, block=blk)

    def chunk_at(t):
        """Chunk index this device's partial holds after hop t."""
        return jnp.mod(rank.astype(jnp.int32) - 1 - t, w)

    def local_chunk(c):
        return lax.dynamic_slice_in_dim(padded, c * chunk, chunk)

    def offs_of(c):
        if padded_offs is not None:
            return lax.dynamic_slice_in_dim(
                padded_offs, c.astype(jnp.int32) * chunk, chunk)
        return (jnp.uint32(offset_start)
                + c.astype(jnp.uint32) * jnp.uint32(chunk)
                + jnp.arange(chunk, dtype=jnp.uint32))

    def hop_rbits(t, c):
        k = jax.random.fold_in(jax.random.fold_in(key, t), 0)
        return sr_bits_at(k, offs_of(c))

    def accum(res, comp, t, c):
        g = local_chunk(c)
        offs = offs_of(c)
        if use_kahan:
            return _hop_kahan(q, res, comp, g, t, offs)
        if fused and not fused_wire:
            # legacy fused hop (unpacked wires): add+cast only
            if key is None:
                from ..ops.quantize import quantize_add_pallas
                return quantize_add_pallas(res, g, exp, man,
                                           interpret=interpret), comp
            from ..ops.quantize import quantize_add_pallas_bits
            return quantize_add_pallas_bits(res, g, exp, man,
                                            hop_rbits(t, c),
                                            interpret=interpret), comp
        return _hop_plain(q, res, g, t, offs, fp32_shortcut), comp

    def to_wire(res, comp):
        payload = jnp.stack([res, comp]) if use_kahan else res
        if block_scale:
            return pack_exmy_blocked(payload, exp, man, block_size)
        return pack_exmy(payload, exp, man) if packed else payload

    def from_wire(p):
        if block_scale:
            payload = unpack_exmy_blocked(p, exp, man, chunk, block_size)
        else:
            payload = unpack_exmy(p, exp, man) if packed else p
        if use_kahan:
            return payload[0], payload[1]
        return payload, jnp.zeros_like(payload)

    def fused_hop(recv_wire, t, c, want_digest):
        """The single-kernel wire path: unpack + add + (scale+)cast +
        pack (+ Fletcher digest of both wire buffers) in ONE Pallas
        kernel (ops/quantize.hop_pack_pallas).  Bitwise identical to the
        XLA composition (same cast/pack bodies)."""
        from ..ops.quantize import hop_pack_pallas
        rb = None if key is None else hop_rbits(t, c)
        return hop_pack_pallas(recv_wire, local_chunk(c), exp, man,
                               rbits=rb, block_size=blk,
                               want_digest=want_digest,
                               interpret=interpret)

    def fused_first(c, want_digest):
        from ..ops.quantize import quantize_pack_pallas
        rb = None if key is None else hop_rbits(jnp.int32(0), c)
        return quantize_pack_pallas(local_chunk(c), exp, man, rbits=rb,
                                    block_size=blk,
                                    want_digest=want_digest,
                                    interpret=interpret)

    if not verify and fault is None:
        # the plain transport, untouched: zero checksum work, and the
        # oracle-parity tests gate this exact path bitwise
        if fused_wire:
            _, wire0 = fused_first(chunk_at(0), False)

            def body(carry, t):
                with jax.named_scope(scopes.WIRE_COLLECTIVE):
                    recv = lax.ppermute(carry, axis_name, perm)
                _, new_wire = fused_hop(recv, t, chunk_at(t), False)
                return new_wire, None

            carry, _ = lax.scan(body, wire0,
                                jnp.arange(1, w, dtype=jnp.int32))
            res, _ = from_wire(carry)
        else:
            zero = jnp.zeros((chunk,), jnp.float32)
            res, comp = accum(zero, zero, jnp.int32(0), chunk_at(0))

            def body(carry, t):
                with jax.named_scope(scopes.WIRE_COLLECTIVE):
                    recv = lax.ppermute(carry, axis_name, perm)
                res, comp = from_wire(recv)
                res, comp = accum(res, comp, t, chunk_at(t))
                return to_wire(res, comp), None

            carry, _ = lax.scan(body, to_wire(res, comp),
                                jnp.arange(1, w, dtype=jnp.int32))
            res, _ = from_wire(carry)
        # res is now the reduced chunk `rank`; ring all-gather of the
        # packed chunks rebuilds the full vector (XLA lowers all_gather
        # as a ring on the TPU torus, so the wire cost is the (W-1)
        # chunk hops accounted in ring_transport_bytes — with the
        # payload still bit-packed).  On the fused arm the scan's final
        # carry IS that packed wire (the kernel canonicalizes its code
        # bytes to the XLA re-pack's exactly), so no re-pack runs.
        if fused_wire:          # fused_wire already excludes Kahan
            wire = carry
        elif block_scale:
            wire = pack_exmy_blocked(res, exp, man, block_size)
        else:
            wire = pack_exmy(res, exp, man) if packed else res
        with jax.named_scope(scopes.WIRE_COLLECTIVE):
            gathered = lax.all_gather(wire, axis_name, axis=0, tiled=False)
        if block_scale:
            full = jax.vmap(lambda r: unpack_exmy_blocked(
                r, exp, man, chunk, block_size))(gathered)
        else:
            full = (unpack_exmy(gathered, exp, man) if packed
                    else gathered)
        return full.reshape(-1)[:n]

    # --- verified / fault-injected transport (module docstring) ------
    #
    # Deferred end-to-end tag compare: the scan carry stays EXACTLY the
    # clean wire (no second per-hop collective — a tag ppermute inside
    # the scan measured 3-4x the whole clean reduce on the CPU mesh);
    # each hop instead RECORDS two uint32 tags as scan outputs — the
    # sender-side tag of what it actually sent, and the receiver-side
    # tag of what actually arrived — and ONE post-scan ppermute of the
    # stacked (W-1,) sent-tag vector lines them up for the compare.
    # Detection is content-complete: any corruption whose bytes differ
    # from the genuine payload mismatches, and one whose bytes are
    # identical is a no-op on the sum.
    from .integrity import hop_tag, wire_digest
    rank_i = rank.astype(jnp.int32)
    have_fault = fault is not None
    if have_fault:
        f_code = jnp.asarray(fault[0], jnp.int32)
        f_rank = jnp.asarray(fault[1], jnp.int32)
        on_me = (f_code > 0) & (rank_i == f_rank)
    left = jnp.mod(rank_i - 1, w)

    def tag_of(wire, t, src, digest=None):
        d = wire_digest(wire) if digest is None else digest
        from .integrity import tag_from_digest
        return tag_from_digest(d, t, src)

    def vbody(carry, t):
        wire = carry
        with jax.named_scope(scopes.WIRE_COLLECTIVE):
            recv = lax.ppermute(wire, axis_name, perm)
        if have_fault:
            recv = _apply_hop_fault(recv, wire, f_code,
                                    on_me & (t == jnp.int32(1)))
        ys = ()
        if fused_wire:
            if verify:
                res, new_wire, d_in, d_out = fused_hop(
                    recv, t, chunk_at(t), True)
                # d_out also rides out raw: the LAST hop's out-digest is
                # the digest of this rank's gather wire (gwire == the
                # final carry), so the gather tag needs no XLA re-hash
                ys = (tag_of(recv, t, left, digest=d_in),
                      tag_of(new_wire, t + 1, rank_i, digest=d_out),
                      d_out)
            else:
                _, new_wire = fused_hop(recv, t, chunk_at(t), False)
        else:
            if verify:
                rtag = hop_tag(recv, t, left)
            res, comp = from_wire(recv)
            res, comp = accum(res, comp, t, chunk_at(t))
            new_wire = to_wire(res, comp)
            if verify:
                ys = (rtag, hop_tag(new_wire, t + 1, rank_i))
        return new_wire, ys

    if fused_wire:
        if verify:
            _, wire0, d0 = fused_first(chunk_at(0), True)
            tag0 = tag_of(wire0, jnp.int32(1), rank_i, digest=d0)
        else:
            _, wire0 = fused_first(chunk_at(0), False)
    else:
        zero = jnp.zeros((chunk,), jnp.float32)
        res, comp = accum(zero, zero, jnp.int32(0), chunk_at(0))
        wire0 = to_wire(res, comp)
        if verify:
            tag0 = hop_tag(wire0, jnp.int32(1), rank_i)
    wire_f, ys = lax.scan(vbody, wire0, jnp.arange(1, w, dtype=jnp.int32))
    res, _ = from_wire(wire_f)

    hop_bad = jnp.zeros([], jnp.int32)
    d_gwire = None
    if verify and fused_wire:
        d_gwire = d0  # w == 1: wire0 is the gather wire
    if verify and w > 1:
        if fused_wire:
            rtags, stags, douts = ys
            d_gwire = douts[-1]
        else:
            rtags, stags = ys
        # sent[k] = the tag of the wire delivered at hop k+1: wire0's
        # tag first, then each body-produced wire's (the last body
        # iteration's wire is never sent — its tag is dropped)
        sent = jnp.concatenate([tag0[None], stags[:-1]])
        with jax.named_scope(scopes.WIRE_COLLECTIVE):
            remote_sent = lax.ppermute(sent, axis_name, perm)
        hop_bad = jnp.sum((remote_sent != rtags).astype(jnp.int32))

    # all-gather wire, row-tagged: row i's tag is built by rank i with
    # hop index 0 (scan hops use t >= 1, so no aliasing).  The fused arm
    # reuses the scan's final carry as the gather wire (kernel bytes ==
    # the XLA re-pack's, PR 9 parity) and its kernel digest for the tag.
    if fused_wire:
        gwire = wire_f
    elif block_scale:
        gwire = pack_exmy_blocked(res, exp, man, block_size)
    else:
        gwire = pack_exmy(res, exp, man) if packed else res
    with jax.named_scope(scopes.WIRE_COLLECTIVE):
        gathered = lax.all_gather(gwire, axis_name, axis=0, tiled=False)
    if have_fault:
        # gather-site fault: rank k's RECEIVED copy of row (k+1) mod W
        # is corrupted — only that replica's rebuilt vector diverges,
        # which is the case the cross-replica agreement digest catches
        j = jnp.mod(rank_i + 1, w)
        row = jnp.take(gathered, j, axis=0)
        new_row = jnp.where(f_code == 2, gwire, row)   # stale: own row
        new_row = jnp.where(f_code == 1, _flip_first_bit(row), new_row)
        new_row = jnp.where(f_code == 3, jnp.zeros_like(row), new_row)
        gathered = jnp.where(on_me, gathered.at[j].set(new_row), gathered)
    if block_scale:
        full = jax.vmap(lambda r: unpack_exmy_blocked(
            r, exp, man, chunk, block_size))(gathered)
    else:
        full = (unpack_exmy(gathered, exp, man) if packed else gathered)
    full = full.reshape(-1)[:n]
    if not verify:
        return full

    # one tiny all_gather carries the whole report exchange: each rank's
    # gather-row tag, its gathered-wire digest, and its hop-bad count —
    # totals and the agreement verdict derive locally; only the
    # per-rank gather-row verdicts (which compare the LOCAL copies of
    # the gathered rows) still need one scalar psum.
    #
    # The agreement value is the digest of this rank's WHOLE gathered
    # wire, composed from the per-row digests via `digest_concat` — the
    # rows were just digested for the tag compare, so agreement costs
    # O(W) scalar folds instead of a second full-vector hash pass
    # (digesting the reconstructed fp32 vector measured as a dominant
    # verify cost, docs/PERF.md).  Coverage is unchanged: `full` is a
    # deterministic pure function of the gathered wire (`from_wire` is
    # shared code), so replicas agreeing on every gathered byte agree
    # on the reconstructed vector bit-for-bit.
    from .integrity import digest_concat, tag_from_digest
    if fused_wire:
        # no XLA-side wire digest on the fused arm (ISSUE 12 leg 4):
        # the sent gather wire's digest came out of the LAST hop's pack
        # kernel, and the RECEIVED rows are hashed by the one-pass
        # per-row digest kernel (ops/quantize.digest_rows_pallas)
        from ..ops.quantize import digest_rows_pallas
        gtag = tag_from_digest(d_gwire, jnp.int32(0), rank_i)
        row_digests = digest_rows_pallas(
            gathered.reshape(w, -1), interpret)
    else:
        gtag = hop_tag(gwire, jnp.int32(0), rank_i)
        row_digests = jax.vmap(wire_digest)(gathered)
    row_tags = jax.vmap(
        lambda d, i: tag_from_digest(d, jnp.int32(0), i))(
            row_digests, jnp.arange(w, dtype=jnp.int32))
    row_words = int(np.prod(gathered.shape[1:]))
    full_digest = row_digests[0]
    for i in range(1, w):
        full_digest = digest_concat(full_digest, i * row_words,
                                    row_digests[i])
    with jax.named_scope(scopes.WIRE_COLLECTIVE):
        rep = lax.all_gather(
            jnp.stack([gtag, full_digest, hop_bad.astype(jnp.uint32)]),
            axis_name, axis=0, tiled=False)
    gather_bad = jnp.sum((row_tags != rep[:, 0]).astype(jnp.int32))
    report = {
        "hop_bad": jnp.sum(rep[:, 2].astype(jnp.int32)),
        "gather_bad": lax.psum(gather_bad, axis_name),
        "agree": jnp.all(rep[:, 1] == rep[0, 1]).astype(jnp.int32),
    }
    report["ok"] = ((report["hop_bad"] == 0) & (report["gather_bad"] == 0)
                    & (report["agree"] == 1)).astype(jnp.int32)
    return full, report


def ring_oracle_sum(stacked: jnp.ndarray, exp: int, man: int, *,
                    use_kahan: bool = False, key=None,
                    offset_start: int = 0,
                    offsets: Optional[jnp.ndarray] = None,
                    block_scale: bool = False,
                    block_size: int = 128) -> jnp.ndarray:
    """Single-device oracle for the ring transport: given the stacked
    per-rank contributions (W, *shape), reproduce `ring_quantized_sum`'s
    result bit-for-bit — the per-chunk rank rotation, the per-hop casts
    with their (step, site, global-offset) SR bit indexing, the (8,23)
    fp32 shortcut, and (``block_scale=True``) the block-scaled hop
    quantizer with its chunk-local block boundaries — everything except
    the wire.

    The distributed path and this oracle share the hop-body functions
    (`_hop_plain` / `_hop_kahan` / `_make_hop_q`, the latter carrying
    the blocked cast), so a divergence can only come from the transport
    itself — which is exactly what the oracle-parity tests gate."""
    w = stacked.shape[0]
    n = int(stacked[0].size)
    shape = stacked.shape[1:]
    if n == 0:
        return jnp.zeros(shape, jnp.float32)
    flat = jnp.reshape(jnp.asarray(stacked, jnp.float32), (w, n))
    chunk = ring_chunk_size(n, w)
    padded = jnp.pad(flat, ((0, 0), (0, w * chunk - n)))
    per_chunk = padded.reshape(w, w, chunk)        # [rank, chunk, elem]
    # contribution visiting chunk c at hop t comes from rank (c+1+t) mod w
    t_idx = jnp.arange(w)[:, None]
    c_idx = jnp.arange(w)[None, :]
    order = jnp.mod(c_idx + 1 + t_idx, w)          # [hop, chunk]
    hops = per_chunk[order, c_idx, :]              # [hop, chunk, elem]
    if offsets is not None:
        offs = jnp.pad(offsets.astype(jnp.uint32).reshape(-1),
                       (0, w * chunk - n)).reshape(w, chunk)
    else:
        offs = (jnp.uint32(offset_start)
                + (c_idx.astype(jnp.uint32) * jnp.uint32(chunk))[..., None]
                + jnp.arange(chunk, dtype=jnp.uint32)[None, None, :])[0]
    q = _make_hop_q(exp, man, key,
                    block=block_size if block_scale else None)
    fp32_shortcut = exp == 8 and man == 23 and not use_kahan

    def body(carry, xs):
        res, comp = carry
        t, g = xs
        if use_kahan:
            res, comp = _hop_kahan(q, res, comp, g, t, offs)
        else:
            res = _hop_plain(q, res, g, t, offs, fp32_shortcut)
        return (res, comp), None

    zero = jnp.zeros((w, chunk), jnp.float32)
    (res, _), _ = lax.scan(
        body, (zero, zero), (jnp.arange(w, dtype=jnp.int32), hops))
    return res.reshape(-1)[:n].reshape(shape)


def hierarchical_ring_sum(flat: jnp.ndarray, axis_names, exp: int, man: int,
                          *, use_kahan: bool = False, key=None,
                          offset_start: int = 0,
                          offsets: Optional[jnp.ndarray] = None,
                          packed: bool = True,
                          fused: Optional[bool] = None,
                          interpret: bool = False,
                          verify: bool = False,
                          fault: Optional[tuple] = None,
                          block_scale: bool = False,
                          block_size: int = 128):
    """Ring all-reduce composed over one OR several mesh axes.

    A single axis (plain string, or a 1-tuple) is exactly
    `ring_quantized_sum` — same bits, same program.  For k > 1 axes the
    reduction runs as k sequential per-axis rings, INNERMOST (last-named)
    axis first: per the mesh convention (parallel/mesh.py) the last axis
    is the fastest ICI ring, so the large fan-in happens on the cheap
    wire and the outer axes ring over already-reduced partials — the
    hierarchical intra-axis-then-inter-axis reduce of the MLPerf TPU-pod
    recipe (PAPERS.md #4).  Stage ``s`` reduces over ``axes[-1-s]`` with
    SR key ``fold_in(key, s)`` (stages must draw independent bits — the
    same (hop, site, offset) indices recur at every stage), and the
    result is the per-axis composition of the documented per-chunk rank
    rotation — reproduced bit-for-bit by `ring_oracle_sum_multi`.

    verify → every stage runs the self-verifying transport; the merged
    report sums ``hop_bad`` / ``gather_bad`` across all rings of all
    stages (psum over the non-stage axes makes the totals replicated),
    ANDs the per-stage agreement verdicts, and adds a FINAL cross-mesh
    agreement digest over every axis at once — a divergence introduced
    between stages (or on the last gather wire) cannot hide in a
    single-axis check.

    fault → injected into stage 0 only, and only on the one stage-0 ring
    whose other-axes indices are all zero: exactly ONE corruption fires,
    so the chaos drills' exact counter expectations (one flip →
    hop_bad == 1) hold on any mesh shape.
    """
    axes = ((axis_names,) if isinstance(axis_names, str)
            else tuple(axis_names))
    if not axes:
        raise ValueError("hierarchical_ring_sum needs at least one axis")
    kw = dict(use_kahan=use_kahan, offset_start=offset_start,
              offsets=offsets, packed=packed, fused=fused,
              interpret=interpret, block_scale=block_scale,
              block_size=block_size)
    if len(axes) == 1:
        return ring_quantized_sum(flat, axes[0], exp, man, key=key,
                                  verify=verify, fault=fault, **kw)

    vec = flat
    stage_reports = []
    for s in range(len(axes)):
        ax = axes[-1 - s]
        k_s = None if key is None else jax.random.fold_in(key, s)
        f_s = None
        if fault is not None and s == 0:
            on_slice = jnp.int32(1)
            for other in axes[:-1]:
                on_slice = on_slice * (
                    lax.axis_index(other) == 0).astype(jnp.int32)
            f_s = (jnp.where(on_slice == 1,
                             jnp.asarray(fault[0], jnp.int32),
                             jnp.int32(0)),
                   jnp.asarray(fault[1], jnp.int32))
        out = ring_quantized_sum(vec, ax, exp, man, key=k_s,
                                 verify=verify, fault=f_s, **kw)
        if verify:
            vec, rep = out
            stage_reports.append((ax, rep))
        else:
            vec = out
    if not verify:
        return vec

    from .integrity import digest_agree, wire_digest
    hop_bad = jnp.zeros([], jnp.int32)
    gather_bad = jnp.zeros([], jnp.int32)
    agree = jnp.ones([], jnp.int32)
    for ax, rep in stage_reports:
        other = tuple(a for a in axes if a != ax)
        hop_bad = hop_bad + lax.psum(rep["hop_bad"], other)
        gather_bad = gather_bad + lax.psum(rep["gather_bad"], other)
        agree = jnp.minimum(agree, lax.pmin(rep["agree"], other))
    agree = jnp.minimum(agree, digest_agree(wire_digest(vec), axes))
    report = {"hop_bad": hop_bad, "gather_bad": gather_bad,
              "agree": agree}
    report["ok"] = ((hop_bad == 0) & (gather_bad == 0)
                    & (agree == 1)).astype(jnp.int32)
    return vec, report


def ring_oracle_sum_multi(stacked: jnp.ndarray, n_axes: int, exp: int,
                          man: int, *, use_kahan: bool = False, key=None,
                          offset_start: int = 0,
                          offsets: Optional[jnp.ndarray] = None,
                          block_scale: bool = False,
                          block_size: int = 128) -> jnp.ndarray:
    """Single-device oracle for `hierarchical_ring_sum`: ``stacked`` has
    shape ``(W_0, ..., W_{k-1}, *leaf)`` with the leading dims in mesh
    AXIS-NAME order; the reduction folds the LAST leading axis first
    (the innermost mesh axis), stage ``s`` drawing SR bits from
    ``fold_in(key, s)`` — exactly the distributed composition.  With
    ``n_axes == 1`` this is `ring_oracle_sum` (unfolded key, the legacy
    single-axis bitstream)."""
    if n_axes < 1 or stacked.ndim < n_axes:
        raise ValueError(f"n_axes={n_axes} does not fit stacked shape "
                         f"{stacked.shape}")
    kw = dict(use_kahan=use_kahan, offset_start=offset_start,
              offsets=offsets, block_scale=block_scale,
              block_size=block_size)
    if n_axes == 1:
        return ring_oracle_sum(stacked, exp, man, key=key, **kw)
    vec = stacked
    for s in range(n_axes):
        k_s = None if key is None else jax.random.fold_in(key, s)
        lead = vec.shape[:n_axes - s]
        tail = vec.shape[n_axes - s:]
        rest = int(np.prod(lead[:-1])) if lead[:-1] else 1
        flat = vec.reshape((rest, lead[-1]) + tail)
        red = jax.vmap(lambda st, k=k_s: ring_oracle_sum(
            st, exp, man, key=k, **kw))(flat)
        vec = red.reshape(lead[:-1] + tail)
    return vec


def ring_transport_bytes(n: int, world: int, exp: int, man: int, *,
                         use_kahan: bool = False,
                         packed: bool = True,
                         block_size: Optional[int] = None) -> int:
    """Analytic per-device wire bytes for one ring all-reduce of n
    elements: (W-1) reduce-scatter hops of one chunk (×2 with Kahan — the
    compensation rides) plus (W-1) all-gather hops of one chunk.

    ``block_size`` prices the block-scaled wire: every chunk payload
    carries its sidecar lane (one shift byte per block, odd tail block
    included) next to the code words — the sidecar is EXPLICIT here, and
    tests pin this formula against real `pack_exmy_blocked` buffer
    sizes so the analytics can never silently under-report the wire."""
    if n == 0 or world <= 0:
        return 0
    chunk = ring_chunk_size(n, world)
    if block_size is not None:
        per_chunk = wire_bytes_blocked(exp, man, chunk, block_size)
    else:
        per_chunk = chunk * (wire_bytes(exp, man) if packed else 4)
    reduce_phase = (world - 1) * per_chunk * (2 if use_kahan else 1)
    gather_phase = (world - 1) * per_chunk
    return reduce_phase + gather_phase


def gather_transport_bytes(n: int, world: int, exp: int, man: int, *,
                           compressed: bool = False,
                           block_size: Optional[int] = None) -> int:
    """Analytic per-device wire bytes for the faithful all_gather path:
    (W-1)·n elements, raw fp32 unless the APS-prequantized wire packing
    applies (`compressed`).  ``block_size`` adds the sidecar bytes a
    block-scaled row would carry ((W-1) rows × one shift byte per
    block) — analytic only; the faithful gather ships per-tensor today,
    but the ledger must price the alternative honestly."""
    if n == 0 or world <= 0:
        return 0
    if block_size is not None:
        return (world - 1) * wire_bytes_blocked(exp, man, n, block_size)
    per_elem = wire_bytes(exp, man) if compressed else 4
    return (world - 1) * n * per_elem


def transport_table(n: int, world: int, exp: int, man: int,
                    use_kahan: bool = False,
                    block_size: Optional[int] = None) -> dict:
    """Analytic per-device bytes-on-wire for every transport of one
    all-reduce of n elements — the payload of bench.py's `reduction`
    block and tools/bench_reduce.py.  One home for the comparison so the
    ledger, the tool and docs/PERF.md's table cannot drift.  With
    ``block_size`` the table adds the block-scaled ring row (code words
    + sidecar lane, both counted)."""
    compressible = man >= 2 and wire_bytes(exp, man) < 4
    gather = gather_transport_bytes(n, world, exp, man, compressed=False)
    table = {
        "faithful_gather_fp32": gather,
        "faithful_gather_packed": (
            gather_transport_bytes(n, world, exp, man, compressed=True)
            if compressible else None),  # needs APS pre-quantized values
        "ring_packed": ring_transport_bytes(n, world, exp, man,
                                            use_kahan=use_kahan,
                                            packed=compressible),
        "ring_block_scaled": (
            ring_transport_bytes(n, world, exp, man, use_kahan=use_kahan,
                                 block_size=block_size)
            if block_size is not None and compressible else None),
        # XLA lowers psum as a ring reduce-scatter + all-gather on the
        # TPU torus, but the payload stays fp32 (psum cannot know the
        # values fit a narrower format — EQuARX's whole point), so fast
        # mode's wire is exactly the UNPACKED ring: 2·(W-1)·(n/W)·4
        "fast_psum_fp32": ring_transport_bytes(n, world, 8, 23,
                                               packed=False),
    }
    table["ring_vs_gather_ratio"] = (
        round(gather / table["ring_packed"], 2) if table["ring_packed"]
        else None)
    return table


def ir_programs(reg):
    """Program-contract declarations (analysis/ir/registry.py).

    The ring transport and the faithful gather are the wire the byte
    analytics above price — each registered arm carries a ``wire``
    contract equal to its analytic table entry, so a stray fp32 debug
    gather, an unpacked hop, or a dropped block sidecar fails the
    ``ir-wire-ledger`` rule instead of silently shipping unpriced
    bytes.  All arms are bitwise-gated (`ring_oracle_sum` parity is a
    cross-program bitwise claim)."""
    from jax.sharding import PartitionSpec as P

    from ..compat import shard_map
    from .mesh import data_parallel_mesh

    W, n = 8, 1000
    deps = ("cpd_tpu.quant.numerics", "cpd_tpu.parallel.ring",
            "cpd_tpu.parallel.reduction")

    def _ring(use_kahan=False, block=None, exp=5, man=2):
        def build():
            mesh = data_parallel_mesh()

            def body(x):
                return ring_quantized_sum(
                    x[0], "dp", exp, man, use_kahan=use_kahan,
                    world=W, block_scale=block is not None,
                    block_size=block if block is not None else 128)

            fn = shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                           out_specs=P(), check_vma=False)
            return fn, (jax.ShapeDtypeStruct((W, n), jnp.float32),)
        return build

    reg.declare("ring.packed[e5m2,w8]", _ring(),
                deps=deps, axis_sizes={"dp": W}, bitwise=True,
                wire=lambda: ring_transport_bytes(n, W, 5, 2))
    reg.declare("ring.kahan[e5m2,w8]", _ring(use_kahan=True),
                deps=deps, axis_sizes={"dp": W}, bitwise=True,
                wire=lambda: ring_transport_bytes(n, W, 5, 2,
                                                  use_kahan=True))
    reg.declare("ring.blocked[e4m3,b32,w8]", _ring(block=32, exp=4,
                                                   man=3),
                deps=deps, axis_sizes={"dp": W}, bitwise=True,
                wire=lambda: ring_transport_bytes(n, W, 4, 3,
                                                  block_size=32))

    def _gather(use_aps):
        def build():
            from .dist import sum_gradients
            mesh = data_parallel_mesh()

            def body(g):
                return sum_gradients({"g": g[0]}, "dp", use_aps=use_aps,
                                     grad_exp=5, grad_man=2,
                                     mode="faithful")

            fn = shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                           out_specs=P(), check_vma=False)
            return fn, (jax.ShapeDtypeStruct((W, n), jnp.float32),)
        return build

    gdeps = deps + ("cpd_tpu.parallel.dist", "cpd_tpu.parallel.aps")
    reg.declare("gather.fp32[e5m2,w8]", _gather(False),
                deps=gdeps, axis_sizes={"dp": W}, bitwise=True,
                wire=lambda: gather_transport_bytes(n, W, 5, 2,
                                                    compressed=False))
    reg.declare("gather.packed[aps,e5m2,w8]", _gather(True),
                deps=gdeps, axis_sizes={"dp": W}, bitwise=True,
                wire=lambda: gather_transport_bytes(n, W, 5, 2,
                                                    compressed=True))
