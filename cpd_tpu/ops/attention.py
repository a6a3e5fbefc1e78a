"""Attention ops: fused local attention + ring attention for sequence/context
parallelism.

The reference has no attention at all (SURVEY.md §5 "long-context: absent" —
its workloads are CNNs), so this is new capability, built TPU-first:

* `local_attention` — plain blockwise softmax attention on one device;
  fp32 logits/softmax (MXU matmuls in the input dtype, accumulation fp32).
* `ring_attention` — sequence-parallel attention inside `shard_map`: Q
  stays resident, K/V blocks rotate around the `sp` axis ring via
  `lax.ppermute` while an online-softmax accumulator (running max m,
  normalizer l, output o) folds in one block per step.  Communication is
  W-1 ppermutes of the local K/V — the ICI-friendly pattern of Ring
  Attention (Liu et al.; see PAPERS.md) — and peak memory is O(T_local^2)
  per device instead of O(T^2).
* `ulysses_attention` — the all-to-all alternative (DeepSpeed-Ulysses
  pattern; see PAPERS.md): one all_to_all turns sequence sharding into
  head sharding, each device runs *full-sequence* attention on H/W heads,
  a second all_to_all restores sequence sharding.  Two collectives total
  (vs W-1 permute rounds), at the price of requiring heads % W == 0 and
  O((T_global)^2) score memory per device — the right trade when W is
  modest and heads are plentiful; composable with `impl="flash"` to drop
  the score-matrix memory.
* `grouped_query_attention` — GQA on UNEXPANDED K/V (H_kv heads serving
  H = rep*H_kv query heads, kv head j ↔ q heads [j*rep, (j+1)*rep)):
  the query head axis is reshaped to (H_kv, rep) and contracted against
  the small K/V directly, so neither HBM nor the score computation ever
  materializes the repeated copies — this is what makes the GQA KV-cache
  memory win real at decode time.  The sequence-parallel paths carry the
  SAME unexpanded K/V through their collectives (round 4): the ring
  rotates (B, T_local, H_kv, D) blocks — rep× fewer ICI bytes than the
  expanded path, the point of GQA under sp — and ulysses all_to_alls
  H_kv-headed K/V whenever H_kv divides the axis size, expanding by the
  minimal factor (worst case to H) only when it does not.

Causality with a sharded sequence: rank r holds tokens
[r*T_local, (r+1)*T_local); at ring step s it receives the K/V block of
rank (r-s) mod W.  Blocks from lower-ranked sources attend fully, the own
block (s=0) uses the triangular mask, and blocks from higher-ranked
sources are skipped (masked to -inf; their compute overlaps the permute).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["local_attention", "ring_attention", "ulysses_attention",
           "grouped_query_attention"]

_NEG_INF = -1e30  # large-negative instead of -inf: keeps softmax NaN-free
                  # when a full row is masked (the all-masked ring step)

# Fully-masked query rows (a causal shard whose every key is in the
# future, e.g. q_offset + Tq <= k_offset) return ZERO in every impl —
# the flash-attention convention (round 5, ADVICE r4): the one-shot
# softmax's uniform-average fallback and the online-softmax paths'
# pad-key pollution both produced arbitrary, impl-dependent values for
# rows with no attendable key; zero is the one answer all schedules
# (one-shot, chunked, ring, Pallas flash_gqa) can agree on exactly.


def _causal_mask(tq: int, tk: int, q_off, k_off) -> jnp.ndarray:
    """(tq, tk) bool mask: query global position >= key global position."""
    qi = q_off + jnp.arange(tq)[:, None]
    ki = k_off + jnp.arange(tk)[None, :]
    return qi >= ki


def local_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True,
                    q_offset=0, k_offset=0,
                    impl: str = "xla") -> jnp.ndarray:
    """Softmax attention for (B, T, H, D) tensors on one device.

    fp32 softmax; returns q.dtype.  Offsets give the tokens' global
    positions (used by ring steps and by tests comparing shard vs full).

    impl="flash" opts into the Pallas TPU flash-attention kernel
    (jax.experimental.pallas.ops.tpu) — O(T) memory instead of the
    materialized (T, T) score matrix.  Explicit opt-in, not autodetected:
    the kernel has TPU-generation/shape constraints (sequence multiples
    of the block size, supported head dims) that should fail loudly at
    the call site, not silently downgrade mid-training.

    impl="chunked" is the pure-XLA flash-style fallback: an online-
    softmax `lax.scan` over K/V blocks — same O(T·block) memory shape as
    flash without the Pallas constraints, any backend, offsets
    supported.  Use when the Pallas kernel's shape rules bite (or off
    TPU); ~the same FLOPs as "xla", traded against score-matrix HBM."""
    if impl == "flash":
        return _flash_attention(q, k, v, causal, q_offset, k_offset)
    if impl == "chunked":
        return _chunked_attention(q, k, v, causal, q_offset, k_offset)
    if impl != "xla":
        raise ValueError(f"unknown attention impl {impl!r}; "
                         "expected 'xla', 'flash' or 'chunked'")
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = None
    if causal:
        mask = _causal_mask(q.shape[1], k.shape[1], q_offset, k_offset)
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    if mask is not None:
        # zero fully-masked rows (softmax fell back to a uniform average)
        out = jnp.where(mask.any(-1)[None, :, None, None], out, 0.0)
    return out.astype(q.dtype)


def _flash_attention(q, k, v, causal, q_offset, k_offset):
    """Pallas TPU flash kernel on (B, T, H, D) inputs (kernel layout is
    (B, H, T, D)); nonzero offsets are not supported — the ring wrapper
    handles global positions itself."""
    if q_offset != 0 or k_offset != 0:
        raise ValueError("impl='flash' does not support q/k offsets; "
                         "use the default impl inside ring steps")
    from ..compat import flash_attention_import
    flash_attention = flash_attention_import()

    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention(qt, kt, vt, causal=causal,
                          sm_scale=1.0 / float(q.shape[-1]) ** 0.5)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


_CHUNK = 512  # K/V block length of the chunked scan (MXU-friendly, and
              # small enough that (B,H,Tq,_CHUNK) fp32 logits stay modest)


def _fold_segment(o, m, l, qg, k_cur, v_cur, valid, scale):
    """One online-softmax fold: merge a K/V segment into the (o, m, l)
    accumulator — the flash recurrence, shared verbatim by the chunked
    scan, the ring per-step fold, and the ring's chunked inner loop.

    qg: (B, Tq, H_kv, rep, D) grouped queries (GQA-native contraction);
    k_cur/v_cur: (B, S, H_kv, D); valid: (Tq, S) bool mask or None."""
    b, tq, hkv, rep, d = qg.shape
    h = hkv * rep
    s = k_cur.shape[1]
    logits = jnp.einsum(
        "bqgrd,bkgd->bgrqk", qg, k_cur,
        preferred_element_type=jnp.float32).reshape(b, h, tq, s) * scale
    if valid is not None:
        logits = jnp.where(valid[None, None], logits, _NEG_INF)
    m_new = jnp.maximum(m, logits.max(axis=-1))          # (B,H,Tq)
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new[..., None])               # (B,H,Tq,S)
    if valid is not None:
        # explicit zero, not exp(_NEG_INF - m): when the whole row is
        # still masked m_new == _NEG_INF and exp(0) == 1 would count
        # every masked/pad key into l (ADVICE r4 — degenerate rows now
        # yield l == 0 -> output 0, matching the one-shot path's zeroed
        # fully-masked rows)
        p = jnp.where(valid[None, None], p, 0.0)
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum(
        "bgrqk,bkgd->bqgrd",
        p.astype(v_cur.dtype).reshape(b, hkv, rep, tq, s),
        v_cur, preferred_element_type=jnp.float32).reshape(
            b, tq, h, v_cur.shape[-1])
    return o * alpha.transpose(0, 2, 1)[..., None] + pv, m_new, l_new


def _chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       causal, q_offset, k_offset,
                       block: int = _CHUNK) -> jnp.ndarray:
    """Flash-style attention in pure XLA: online softmax over K/V blocks.

    Supports GQA natively — q (B, Tq, H, D) against k (B, Tk, H_kv, D)
    and v (B, Tk, H_kv, Dv) with H_kv | H — via the same grouped
    contraction as `grouped_query_attention`, so no expansion is
    materialized either.  Dv may differ from D (latent attention); the
    softmax scale is 1/sqrt(D).
    Peak score memory is (B, H, Tq, block) instead of (B, H, Tq, Tk) —
    in the BACKWARD pass too: the scan body is `jax.checkpoint`ed, so AD
    stores only the per-block (o, m, l) carries (O(Tq·D) each, smaller
    than a block of scores whenever D < block) and recomputes the block
    softmax in the reverse sweep, the flash-backward recipe.  Tk is
    padded to a block multiple (block itself is clamped to ~Tk rounded
    up to the 128-lane width, so short sequences don't pay for a full
    default block of masked pad); pad keys are masked out by their
    global position, so results match the one-shot softmax to fp32
    round-off (same recurrence as `ring_attention`'s fold).
    """
    b, tq, h, d = q.shape
    tk = k.shape[1]
    hkv = k.shape[2]
    rep = _gqa_rep(q, k)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    qg = q.reshape(b, tq, hkv, rep, d)

    block = min(block, max(128, -(-tk // 128) * 128))
    n_blocks = -(-tk // block)
    pad = n_blocks * block - tk
    kp = jnp.pad(k.astype(q.dtype), ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v.astype(q.dtype), ((0, 0), (0, pad), (0, 0), (0, 0)))
    # (N, B, block, H_kv, D) — scan carries one block at a time
    kb = kp.reshape(b, n_blocks, block, hkv, d).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(b, n_blocks, block, hkv, v.shape[-1]).transpose(
        1, 0, 2, 3, 4)
    qi = q_offset + jnp.arange(tq)[:, None]            # (tq, 1)

    def step(carry, xs):
        o, m, l, i = carry
        k_cur, v_cur = xs
        ki = k_offset + i * block + jnp.arange(block)[None, :]
        valid = (ki - k_offset) < tk                   # pad keys out
        if causal:
            valid = valid & (qi >= ki)
        o, m, l = _fold_segment(o, m, l, qg, k_cur, v_cur, valid, scale)
        return (o, m, l, i + 1), None

    o0 = jnp.zeros((b, tq, h, v.shape[-1]), jnp.float32)
    m0 = jnp.full((b, h, tq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    (o, m, l, _), _ = lax.scan(
        jax.checkpoint(step), (o0, m0, l0, jnp.zeros([], jnp.int32)),
        (kb, vb))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _gqa_rep(q: jnp.ndarray, k: jnp.ndarray) -> int:
    """Query-heads-per-kv-head factor, validated (1 = MHA)."""
    h, hkv = q.shape[2], k.shape[2]
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    return h // hkv


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str, causal: bool = True,
                   impl: str = "xla", block: int = _CHUNK) -> jnp.ndarray:
    """Sequence-parallel attention; call inside shard_map with the sequence
    dim sharded over `axis_name`.

    q: (B, T_local, H, D); k, v: (B, T_local, H_kv, D) with H_kv | H — GQA
    K/V ride the ring UNEXPANDED (rep× fewer ppermute bytes; the per-step
    contraction groups the query heads instead — the same dot products as
    the expanded ring, agreeing to the last ulp of the fp32 softmax chain;
    XLA's batched-matmul layout for the grouped einsum differs, so not
    bitwise).  Returns (B, T_local, H, D).  Differentiable (ppermute
    transposes to the reverse permute, so the backward pass is itself a
    ring).

    impl="chunked" folds each received K/V block through an inner
    checkpointed sub-block scan (the same `_fold_segment` recurrence):
    per-step score memory drops from (B, H, T_local, T_local) to
    (B, H, T_local, block) — forward and backward — which is what keeps
    very long per-device shards (T_local ≫ block) inside HBM.  When
    block does not divide T_local, the largest divisor of T_local that
    is ≤ block is used instead (the memory bound is preserved or
    bettered, never silently dropped); a DEGENERATE split (divisor
    < min(block, 128), e.g. prime T_local) raises rather than scanning
    element-by-element or materializing the full block.
    """
    if impl not in ("xla", "chunked"):
        raise ValueError(f"unknown ring impl {impl!r}; "
                         "expected 'xla' or 'chunked'")
    axis_size = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    hkv = k.shape[2]
    rep = _gqa_rep(q, k)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    q_off = my * t_local
    # grouped layout: head index h == g*rep + r, so reshaping (H,) to
    # (H_kv, rep) keeps kv head g serving q heads [g*rep, (g+1)*rep)
    qg = q.reshape(b, t_local, hkv, rep, d)
    if impl == "chunked" and t_local > block:
        # largest divisor of T_local <= block: the opted-into memory
        # bound must hold, so never fall back to one whole-block fold
        div = max(f for f in range(1, block + 1) if t_local % f == 0)
        # refuse only when the REQUESTED block couldn't be honored and
        # the best divisor is tiny (e.g. prime T_local -> div == 1); an
        # explicit small block that divides exactly is always accepted
        if div != block and div < max(8, block // 16):
            raise ValueError(
                f"ring impl='chunked' cannot split T_local={t_local} "
                f"into sub-blocks <= {block}: largest divisor is {div} "
                f"(degenerate).  Pick a per-device sequence length "
                f"divisible by the block (multiples of 128 recommended) "
                f"or pass an explicit block= that divides it")
        block = div
        n_inner = t_local // block
    else:
        n_inner, block = 1, t_local

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    qi = q_off + jnp.arange(t_local)[:, None]

    def step(carry, s):
        o, m, l, k_cur, v_cur = carry
        src = (my - s) % axis_size           # whose K/V block we hold
        k_off = src * t_local

        def fold(inner_carry, xs):
            o_i, m_i, l_i, j = inner_carry
            k_seg, v_seg = xs
            valid = None
            if causal:
                ki = k_off + j * block + jnp.arange(block)[None, :]
                valid = qi >= ki
            o_i, m_i, l_i = _fold_segment(o_i, m_i, l_i, qg, k_seg,
                                          v_seg, valid, scale)
            return (o_i, m_i, l_i, j + 1), None

        if n_inner == 1:
            (o_new, m_new, l_new, _), _ = fold(
                (o, m, l, jnp.zeros([], jnp.int32)), (k_cur, v_cur))
        else:
            ks = k_cur.reshape(b, n_inner, block, hkv, d).transpose(
                1, 0, 2, 3, 4)
            vs = v_cur.reshape(b, n_inner, block, hkv, d).transpose(
                1, 0, 2, 3, 4)
            (o_new, m_new, l_new, _), _ = lax.scan(
                jax.checkpoint(fold),
                (o, m, l, jnp.zeros([], jnp.int32)), (ks, vs))

        # rotate K/V to the next rank (skip after the last fold: the scan
        # body is uniform, so we permute every step; the final permute
        # restores the original placement, which XLA can DCE if unused)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt), None

    o0 = jnp.zeros(q.shape[:2] + (q.shape[2], v.shape[-1]), jnp.float32)
    m0 = jnp.full((q.shape[0], q.shape[2], t_local), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((q.shape[0], q.shape[2], t_local), jnp.float32)
    (o, m, l, _, _), _ = lax.scan(
        step, (o0, m0, l0, k.astype(q.dtype), v.astype(q.dtype)),
        jnp.arange(axis_size))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def grouped_query_attention(q: jnp.ndarray, k: jnp.ndarray,
                            v: jnp.ndarray, causal: bool = True,
                            q_offset=0, impl: str = "xla") -> jnp.ndarray:
    """GQA softmax attention without materializing the K/V expansion.

    q: (B, Tq, H, D) with H = rep * H_kv; k, v: (B, Tk, H_kv, D).
    Numerically identical to expanding K/V over each query group and
    calling `local_attention` (fp32 logits/softmax, same mask), tested
    bitwise-close against that oracle.  rep == 1 falls through to
    `local_attention` itself.

    impl="flash" routes MHA (H == H_kv) to the stock TPU flash-attention
    kernel and GQA to the in-repo GQA-native Pallas kernel
    (`ops/flash_gqa.py`) which consumes the unexpanded K/V directly.
    tools/pallas_check.py on a v5e chip (2026-09-26, libtpu 0.0.34): both
    forwards compile and agree with the XLA reference to 2e-2 (fp32
    matmuls run as bf16 passes there), flash_gqa also at short Tq
    (q block 8 and 40); its gradient is the pair of Pallas flash-backward
    kernels, checked there at the benchmark's own shapes.  Timings of
    both passes beside the chunked XLA path: PERF.md section 6, PR 31.
    impl="chunked" runs the grouped contraction through the
    online-softmax K/V-block scan (`_chunked_attention`) — GQA-native,
    O(Tq·block) score memory, any backend.
    """
    b, tq, h, d = q.shape
    hkv = k.shape[2]
    if impl == "flash" and h != hkv:
        # GQA-native Pallas kernel (round 5): grouped queries against the
        # UNEXPANDED K/V — nothing rep-sized is materialized in HBM
        if q_offset != 0:
            raise ValueError("impl='flash' does not support q offsets; "
                             "use the default impl inside ring steps")
        from .flash_gqa import flash_gqa
        return flash_gqa(q, k, v, causal)
    if impl == "chunked":
        return _chunked_attention(q, k, v, causal, q_offset, 0)
    if h == hkv:
        return local_attention(q, k, v, causal=causal, q_offset=q_offset,
                               impl=impl)
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    rep = h // hkv
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    qg = q.reshape(b, tq, hkv, rep, d)
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    mask = None
    if causal:
        mask = _causal_mask(tq, k.shape[1], q_offset, 0)
        logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    if mask is not None:
        out = jnp.where(mask.any(-1)[None, :, None, None, None], out, 0.0)
    return out.reshape(b, tq, h, d).astype(q.dtype)


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      axis_name: str, causal: bool = True,
                      impl: str = "xla") -> jnp.ndarray:
    """All-to-all sequence-parallel attention; call inside shard_map with
    the sequence dim sharded over `axis_name`.

    q, k, v: (B, T_local, H, D) local shards with H the device-local head
    count (after any tensor-parallel split); H must be divisible by the
    `axis_name` mesh size (all_to_all enforces this).  Returns
    (B, T_local, H, D).  Differentiable: all_to_all transposes to the
    reverse all_to_all.

    GQA K/V (H_kv < H heads) go through the all_to_all UNEXPANDED whenever
    H_kv is divisible by the axis size — rep× fewer ICI bytes — and the
    full-sequence middle step runs the grouped kernel on each device's
    contiguous head chunk (chunk w's q heads [w·H/W, (w+1)·H/W) are served
    exactly by its kv heads [w·H_kv/W, (w+1)·H_kv/W), since H/W =
    rep·H_kv/W).  When H_kv % W != 0 the K/V are expanded by the MINIMAL
    factor e (the smallest divisor of rep making H_kv·e % W == 0; worst
    case e = rep, the fully-expanded legacy behavior).

    ``impl`` is forwarded to the full-sequence middle step ("flash" =
    Pallas kernel on the gathered sequence).  With GQA the middle step
    runs the GQA-native flash kernel (`ops/flash_gqa.py`) directly on the
    unexpanded K/V chunk — since round 5 neither the wire NOR device-local
    HBM pays the rep× (the pre-round-5 path re-materialized the expansion
    after the all_to_all).
    """
    axis_size = lax.psum(1, axis_name)
    rep = _gqa_rep(q, k)
    if q.shape[2] % axis_size:
        raise ValueError(f"ulysses needs q heads {q.shape[2]} divisible "
                         f"by the {axis_name} axis size {axis_size}")
    if k.shape[2] % axis_size:
        # minimal grouping-preserving expansion: kv head j repeated e×
        # keeps q head h served by expanded head h // (rep/e), which the
        # contiguous all_to_all chunking preserves iff e | rep
        e = next(f for f in range(1, rep + 1)
                 if rep % f == 0 and (k.shape[2] * f) % axis_size == 0)
        k = jnp.repeat(k, e, axis=2)
        v = jnp.repeat(v, e, axis=2)

    def seq_to_heads(x):
        # (B, T_local, H, D) -> (B, T_global, H/W, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = grouped_query_attention(qh, kh, vh, causal=causal, impl=impl)
    return heads_to_seq(out)
