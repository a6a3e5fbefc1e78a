"""Serving forward pass for `TransformerLM` over the paged eXmY KV cache.

The flax decode path (`TransformerLM(decode=True)`) owns a dense
(B, T_max) cache collection with ONE scalar position shared by the whole
batch — exactly what continuous batching cannot use: slots in the same
decode batch sit at different positions, join and leave mid-flight, and
their K/V lives in pages, not a contiguous buffer.  So serving runs the
transformer math directly over the param pytree: same ops in the same
order as `models/transformer.py` (fast-variance LayerNorm, head-major
qkv split, RoPE, GQA grouped contraction, gelu MLP, tied embed head),
with attention reading K/V through `kvcache.gather_kv` and per-slot
positions instead of the module's cache variables.  Parity with
``model.apply`` is pinned to fp32 round-off by tests/test_serve.py.

Two jitted programs, both jit-stable in shape:

* ``decode_step`` — ONE token for every slot of the fixed-shape batch
  (S,), free slots masked to the trash page;
* ``prefill_step`` — one CHUNK of one slot's prompt (C tokens, tail
  padded + masked), so a long prompt never stalls the decode batch: the
  engine interleaves one chunk per engine step against ongoing decode.

Quantize-on-append ordering: each layer packs its K/V into the pages
FIRST and attends through the pool AFTER, so every K/V read — including
a token's own chunk — sees the dequantized page bytes.  That makes the
numerics independent of *when* a position was computed (prefill, decode,
or corruption-repair recompute), which is what makes repair-by-recompute
deterministic, and makes the (8,23) path bitwise equal to the fp32
oracle (the codec is lossless there; tests/test_serve.py gates it).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from . import kvcache
from .kvcache import KVCacheConfig
from ..compat import shard_map
from ..ops.backend import interpret_mode
from ..ops.serve_attn import fused_gather_attention
from ..parallel.mesh import AXIS_TENSOR, make_mesh
from ..parallel.ring import gather_transport_bytes
from ..quant.numerics import cast_body, pack_exmy, unpack_exmy
from ..utils.cache import LRUCache

__all__ = ["ModelSpec", "spec_from_model", "make_decode_step",
           "make_prefill_step"]

# jitted step programs keyed by their static configuration, shared across
# engines: a fresh ServeEngine for a warm (spec, cfg) re-uses the compile
# instead of re-tracing (the determinism smoke runs the same trace on two
# fresh engines).  Bounded, matching the make_sum_gradients_fn precedent.
_STEP_CACHE = LRUCache(maxsize=32)

# a Python float, not a jnp scalar: promotes to the same float32(-1e30)
# in `jnp.where`, and stays an inlined literal when `_paged_attention`
# traces INSIDE the fused Pallas kernel (a module-level device array
# would be a captured constant, which pallas_call rejects)
_NEG_INF = -1e30
_LN_EPS = 1e-6   # flax nn.LayerNorm default, matching transformer.py


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """The static facts the serving forward needs about a TransformerLM."""
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: Optional[int]     # None = MHA (fused wqkv layout)
    d_ff: int

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads else self.n_heads


def spec_from_model(model) -> ModelSpec:
    """Extract a ModelSpec from a `TransformerLM` module, failing fast on
    configurations the serving forward does not mirror."""
    if getattr(model, "scan_layers", False):
        raise ValueError("serving needs the unrolled block{i} param "
                         "layout; scan_layers=True is not supported")
    if (model.ffn_exp, model.ffn_man) != (8, 23):
        raise ValueError(
            f"serving mirrors the plain Dense FFN only; quantized-"
            f"accumulator MLP (ffn e{model.ffn_exp}m{model.ffn_man}) is "
            "a training-path feature")
    if model.tp_axis or model.sp_axis:
        raise ValueError("serving is single-device (like decode=True); "
                         "unset tp_axis/sp_axis")
    return ModelSpec(vocab_size=model.vocab_size, d_model=model.d_model,
                     n_layers=model.n_layers, n_heads=model.n_heads,
                     n_kv_heads=model.n_kv_heads, d_ff=model.d_ff)


def _layernorm(x: jnp.ndarray, p: dict) -> jnp.ndarray:
    """flax nn.LayerNorm parity: fast variance (E[x²] − E[x]²), eps 1e-6,
    learned scale+bias."""
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    mean2 = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    var = jnp.maximum(0.0, mean2 - jnp.square(mean))
    y = (x - mean) * jax.lax.rsqrt(var + _LN_EPS)
    return y * p["scale"] + p["bias"]


def _rope(x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """Rotary embedding on (B, T, H, D) with PER-SLOT (B, T) positions —
    the batched sibling of transformer._rope (whose positions are one
    (T,) vector shared by the batch; serving slots each sit elsewhere)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (jnp.log(10000.0) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (B, T, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def _qkv(blk: dict, h: jnp.ndarray, spec: ModelSpec) -> tuple:
    """Mirror Block's head-major projection split: (q, k, v) with q
    (B, T, H, D) and k/v (B, T, H_kv, D) — GQA kv stays UNEXPANDED."""
    b, t, _ = h.shape
    hd = spec.head_dim
    if spec.n_kv_heads is None:
        qkv = h @ blk["wqkv"]["kernel"]
        qkv = qkv.reshape(b, t, spec.n_heads, 3, hd)
        return qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    q = (h @ blk["wq"]["kernel"]).reshape(b, t, spec.n_heads, hd)
    kv = (h @ blk["wkv"]["kernel"]).reshape(b, t, spec.n_kv_heads, 2, hd)
    return q, kv[..., 0, :], kv[..., 1, :]


def _shard_qkv(blk: dict, h: jnp.ndarray, spec: ModelSpec,
               tp: int) -> tuple:
    """This shard's head group of `_qkv`, inside `shard_map`: params
    ride REPLICATED (one in_spec for the whole tree — robust to pytree
    container drift), and each shard slices its own contiguous kernel
    columns by ``axis_index``.  The projection layouts are head-major
    (transformer.py), so a contiguous column window IS a whole head
    group — shard s computes exactly heads [s·H/tp, (s+1)·H/tp), and
    the GQA q-group→kv-head mapping (j -> j // rep) stays shard-local
    because tp divides both H and H_kv."""
    b, t, _ = h.shape
    hd = spec.head_dim
    s = lax.axis_index(AXIS_TENSOR)
    if spec.n_kv_heads is None:
        h_loc = spec.n_heads // tp
        cols = h_loc * 3 * hd                 # 3·hd columns per head
        kern = lax.dynamic_slice_in_dim(blk["wqkv"]["kernel"], s * cols,
                                        cols, axis=1)
        qkv = (h @ kern).reshape(b, t, h_loc, 3, hd)
        return qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    h_loc = spec.n_heads // tp
    kv_loc = spec.n_kv_heads // tp
    wq = lax.dynamic_slice_in_dim(blk["wq"]["kernel"], s * h_loc * hd,
                                  h_loc * hd, axis=1)
    wkv = lax.dynamic_slice_in_dim(blk["wkv"]["kernel"],
                                   s * kv_loc * 2 * hd, kv_loc * 2 * hd,
                                   axis=1)
    q = (h @ wq).reshape(b, t, h_loc, hd)
    kv = (h @ wkv).reshape(b, t, kv_loc, 2, hd)
    return q, kv[..., 0, :], kv[..., 1, :]


def _gather_heads(attn_local: jnp.ndarray, cfg: KVCacheConfig) -> jnp.ndarray:
    """all_gather the per-shard attention outputs over the QUANTIZED
    wire: pack to the cache's eXmY format, gather the code words, unpack
    — the EQuARX move applied to the tp gather.  At (8, 23) the cast is
    SKIPPED: `pack_exmy` there is a lossless byte split of ANY fp32
    (subnormals included), so the gathered heads are bit-identical to
    the tp=1 engine's — the sharded (8,23) bitwise contract rides on
    this.  Sub-fp32 formats quantize the attention output on the wire
    (the documented sharded error bound, docs/SERVING.md).  Shard-major
    concatenation == the original contiguous head order, so the merged
    (B, T, H, D) is layout-identical to `_qkv`'s."""
    if cfg.raw:
        full = lax.all_gather(attn_local, AXIS_TENSOR)
    else:
        x = attn_local
        if (cfg.exp_bits, cfg.man_bits) != (8, 23):
            x = cast_body(x, cfg.exp_bits, cfg.man_bits)
        wire = pack_exmy(x, cfg.exp_bits, cfg.man_bits)
        wire = lax.all_gather(wire, AXIS_TENSOR)
        full = unpack_exmy(wire, cfg.exp_bits, cfg.man_bits)
    full = jnp.moveaxis(full, 0, 2)           # (B, T, tp, h_loc, D)
    b, t = full.shape[:2]
    return full.reshape(b, t, -1, full.shape[-1])


def _paged_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     q_pos: jnp.ndarray,
                     last_pos: jnp.ndarray) -> jnp.ndarray:
    """GQA softmax attention against a gathered capacity window.

    q: (B, T, H, D); k/v: (B, T_cap, H_kv, D) — the slot's whole page
    window; q_pos: (B, T) int32 global query positions; last_pos: (B,)
    the newest LIVE position per slot.  The mask ``key_pos <=
    query_pos`` is both causality and the unwritten-tail guard, the
    same contract as Block._cached_attention.  fp32 softmax; grouped
    contraction, nothing rep-sized materialized."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    ki = jnp.arange(k.shape[1], dtype=jnp.int32)
    # zero the window tail past each slot's newest LIVE position BEFORE
    # any contraction: a freshly reallocated page can still hold a
    # previous tenant's bytes — possibly corrupt ones decoding to NaN —
    # and while the logit mask below gives those positions zero
    # PROBABILITY, 0 * NaN in the value einsum would still poison the
    # output row.  Zeroed K/V make the dead tail inert in both
    # contractions.
    live = (ki[None, :] <= last_pos[:, None])[..., None, None]
    k = jnp.where(live, k, 0.0)                      # (B, T_cap, 1, 1)
    v = jnp.where(live, v, 0.0)
    qg = q.reshape(b, t, hkv, rep, d)
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    mask = ki[None, None, :] <= q_pos[:, :, None]         # (B, T, T_cap)
    logits = jnp.where(mask[:, None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, t, h, d)


def _block(blk: dict, x: jnp.ndarray, positions: jnp.ndarray,
           last_pos: jnp.ndarray, pool: jnp.ndarray,
           digests: jnp.ndarray, layer: int,
           page_rows: jnp.ndarray, page_ids: jnp.ndarray,
           offsets: jnp.ndarray, spec: ModelSpec,
           cfg: KVCacheConfig, qkv_fn, merge_fn,
           fused: bool) -> tuple:
    """One decoder block over the paged cache: project, append-quantized,
    attend-through-pool, MLP.  page_ids/offsets: (N,) flattened targets
    of THIS call's (B·T) new positions (masked lanes -> trash page).

    ``cfg`` is the SHARD VIEW (== the engine config at tp=1): every
    kvcache call below is shard-oblivious.  ``qkv_fn``/``merge_fn`` are
    the tp hooks — identity projection/merge at tp=1, per-shard column
    slice + quantized-wire head gather under shard_map.  ``fused``
    routes the pool read through the one-pass Pallas kernel
    (ops/serve_attn.py) instead of gather_kv + attention, with the
    kernel's as-read page digests verified against the stored ones as a
    BONUS read-path check (the pre-append check stays: the kernel
    gathers post-refresh bytes, which are blessed by construction)."""
    h = _layernorm(x, blk["ln1"])
    q, k, v = qkv_fn(blk, h)
    q = _rope(q, positions)
    k = _rope(k, positions)
    # pre-append integrity check: the refresh below re-digests the page
    # from its POST-write bytes, which would re-bless corruption already
    # in it — so the stored digest is verified against the current bytes
    # first, and the step's verdict rides out to the engine (which
    # discards this dispatch's results and repairs on a nonzero count)
    bad = kvcache.check_digests(pool, digests, layer, page_ids)
    # quantize-on-append BEFORE attention (module docstring: every read
    # sees page bytes, so prefill/decode/repair agree on the value set)
    flat = (-1, cfg.n_kv_heads, cfg.head_dim)
    pool = kvcache.write_kv(pool, layer,
                            kvcache.pack_kv(k.reshape(flat), cfg),
                            kvcache.pack_kv(v.reshape(flat), cfg),
                            page_ids, offsets)
    digests = kvcache.refresh_digests(pool, digests, layer, page_ids)
    if fused:
        attn, read_dig = fused_gather_attention(
            pool[layer], q, page_rows, positions, last_pos,
            page_size=cfg.page_size,
            unpack_fn=lambda kv_pages: kvcache.unpack_kv(kv_pages, cfg),
            attend_fn=_paged_attention,
            interpret=interpret_mode())
        bad = bad + jnp.sum(
            (read_dig != digests[layer][page_rows]).astype(jnp.int32))
    else:
        kc, vc = kvcache.gather_kv(pool, layer, page_rows, cfg)
        attn = _paged_attention(q, kc, vc, positions, last_pos)
    attn = merge_fn(attn)
    attn = attn.reshape(*attn.shape[:-2], spec.n_heads * spec.head_dim)
    x = x + attn @ blk["wo"]["kernel"]

    h = _layernorm(x, blk["ln2"])
    h = jax.nn.gelu(h @ blk["wi"]["kernel"])
    x = x + h @ blk["wo_mlp"]["kernel"]
    return x, pool, digests, bad


def _forward(params: dict, tokens: jnp.ndarray, positions: jnp.ndarray,
             last_pos: jnp.ndarray, pool: jnp.ndarray,
             digests: jnp.ndarray, page_rows: jnp.ndarray,
             page_ids: jnp.ndarray, offsets: jnp.ndarray,
             spec: ModelSpec, cfg: KVCacheConfig, qkv_fn=None,
             merge_fn=None, fused: bool = False) -> tuple:
    """Shared decode/prefill body: embed -> blocks -> ln_f -> tied head.
    tokens/positions: (B, T); last_pos: (B,) newest live position per
    slot; returns ((B, T, V) logits, pool, digests, bad) where ``bad``
    is the summed pre-append digest-mismatch count over all layers (the
    engine discards the dispatch and repairs when it is nonzero).
    ``cfg`` must be the shard view; ``qkv_fn``/``merge_fn``/``fused``
    as in `_block` (defaults are the tp=1 XLA path)."""
    if qkv_fn is None:
        qkv_fn = lambda blk, h: _qkv(blk, h, spec)  # noqa: E731
    if merge_fn is None:
        merge_fn = lambda attn: attn                # noqa: E731
    emb = params["embed"]["embedding"]
    x = emb[tokens].astype(jnp.float32)
    bad = jnp.zeros((), jnp.int32)
    for layer in range(spec.n_layers):
        x, pool, digests, layer_bad = _block(
            params[f"block{layer}"], x, positions, last_pos, pool,
            digests, layer, page_rows, page_ids, offsets, spec, cfg,
            qkv_fn, merge_fn, fused)
        bad = bad + layer_bad
    x = _layernorm(x, params["ln_f"])
    logits = jnp.einsum("btd,vd->btv", x, emb.astype(jnp.float32))
    return logits.astype(jnp.float32), pool, digests, bad


def _page_targets(positions: jnp.ndarray, page_rows: jnp.ndarray,
                  valid: jnp.ndarray, cfg: KVCacheConfig) -> tuple:
    """(page_ids, offsets) for new positions: look the position's page up
    in its slot's page-table row; invalid lanes -> the trash page.

    positions/valid: (B, T); page_rows: (B, max_pages).  Returns flat
    (B·T,) int32 pairs, matching _block's flattened K/V rows."""
    slot_page = jnp.clip(positions // cfg.page_size, 0,
                         page_rows.shape[1] - 1)
    pids = jnp.take_along_axis(page_rows, slot_page, axis=1)
    pids = jnp.where(valid, pids, kvcache.TRASH_PAGE)
    offs = jnp.where(valid, positions % cfg.page_size, 0)
    return pids.reshape(-1), offs.reshape(-1).astype(jnp.int32)


def _serve_mesh(tp: int):
    """The serving tp mesh: the first ``tp`` local devices on the one
    tensor axis.  Fails fast with the fix (the conftest/bench device-
    count forcing) when the platform is too small."""
    devices = jax.devices()
    if len(devices) < tp:
        raise RuntimeError(
            f"tp={tp} needs {tp} devices, have {len(devices)} — force "
            "more virtual CPU devices (XLA_FLAGS="
            "--xla_force_host_platform_device_count=N) before jax "
            "initializes, or lower tp")
    return make_mesh(tp=tp, devices=devices[:tp])


def _check_tp(spec: ModelSpec, cfg: KVCacheConfig) -> None:
    if spec.n_heads % cfg.tp != 0:
        raise ValueError(
            f"tp={cfg.tp} must divide n_heads={spec.n_heads}: decode "
            "shards by whole query-head groups")
    if spec.kv_heads % cfg.tp != 0:
        raise ValueError(
            f"tp={cfg.tp} must divide n_kv_heads={spec.kv_heads}")


def make_decode_step(spec: ModelSpec, cfg: KVCacheConfig,
                     fused: bool = False):
    """Jitted fixed-shape continuous-batching decode step.

    fn(params, pool, digests, tokens (S,), positions (S,), page_rows
    (S, max_pages), active (S,) bool) -> (pool, digests, logits (S, V),
    bad).  Each active slot feeds ONE token sitting at ``positions[s]``
    (appending its K/V there) and gets the next-token logits; inactive
    slots ride along masked to the trash page.

    ``cfg.tp > 1`` runs the step under `shard_map` on the serving tp
    mesh: params replicated, pool/digests sharded on their shard axis,
    per-shard projections + attention, and the head merge over the
    quantized all_gather wire (`_gather_heads` — bitwise == tp=1 at
    (8, 23)).  ``fused`` routes the pool read through the one-pass
    Pallas kernel; it is a retrace coordinate (`ladder_step_key`
    carries it) and composes with tp.  The fp32 oracle cache keeps the
    XLA read path — ``fused`` with ``raw=True`` is rejected.  The kernel
    does not compile on TPU (Mosaic has no `dynamic_slice`), so there
    ``fused`` raises with the compiler's message: it runs only in the
    CPU interpreter, as the bitwise gate of a design to be rebuilt."""
    if fused and cfg.raw:
        raise ValueError(
            "fused_attn with raw=True: the fp32 oracle cache is the "
            "reference the fused kernel is gated against — it keeps "
            "the XLA read path")
    if fused and not interpret_mode():
        raise NotImplementedError(
            "fused_attn=True does not compile on TPU (found on v5e, "
            "libtpu 0.0.34, tools/pallas_check.py): Mosaic reports "
            "'Unimplemented primitive in Pallas TPU lowering for "
            "KernelType.TC: dynamic_slice' — ops/serve_attn.py gathers "
            "pages by dynamic index out of a whole-pool VMEM load.  Use "
            "the default XLA read path")
    _check_tp(spec, cfg)

    def build():
        if cfg.tp == 1:
            @jax.jit
            def step(params, pool, digests, tokens, positions, page_rows,
                     active):
                pos2 = positions[:, None]             # (S, 1)
                pids, offs = _page_targets(pos2, page_rows,
                                           active[:, None], cfg)
                logits, pool2, digests2, bad = _forward(
                    params, tokens[:, None], pos2, positions, pool,
                    digests, page_rows, pids, offs, spec, cfg,
                    fused=fused)
                return pool2, digests2, logits[:, 0], bad

            return step

        mesh = _serve_mesh(cfg.tp)
        sv = cfg.shard_view()
        qkv_fn = lambda blk, h: _shard_qkv(blk, h, spec, cfg.tp)  # noqa: E731
        merge_fn = lambda attn: _gather_heads(attn, cfg)          # noqa: E731

        def body(params, pool, digests, tokens, positions, page_rows,
                 active):
            # squeeze this shard's slice to the legacy tp=1 layout —
            # every kvcache call inside _forward is shard-oblivious
            pool = pool[:, :, 0]
            digests = digests[:, :, 0]
            pos2 = positions[:, None]
            pids, offs = _page_targets(pos2, page_rows, active[:, None],
                                       cfg)
            logits, pool, digests, bad = _forward(
                params, tokens[:, None], pos2, positions, pool, digests,
                page_rows, pids, offs, spec, sv, qkv_fn=qkv_fn,
                merge_fn=merge_fn, fused=fused)
            # one fleet-visible verdict: any shard's mismatch is the
            # engine's mismatch (psum is NOT a priced transport)
            bad = lax.psum(bad, AXIS_TENSOR)
            return (pool[:, :, None], digests[:, :, None],
                    logits[:, 0], bad)

        shard = P(None, None, AXIS_TENSOR)
        return jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P(), shard, shard, P(), P(), P(), P()),
            out_specs=(shard, shard, P(), P()), check_vma=False))

    return _STEP_CACHE.get_or_create(("decode", spec, cfg, fused), build)


def make_prefill_step(spec: ModelSpec, cfg: KVCacheConfig, chunk: int):
    """Jitted chunked-prefill step for ONE slot.

    fn(params, pool, digests, tokens (C,), start, n_valid, page_row
    (max_pages,)) -> (pool, digests, last_logits (V,), bad): feeds
    prompt positions [start, start + n_valid) (the (C,) buffer's tail
    past n_valid is pad — masked to the trash page, its rows discarded)
    and returns the logits at the chunk's LAST VALID position —
    meaningful only for the prompt's final chunk, where it samples
    token 0.  ``cfg.tp > 1`` shards exactly like `make_decode_step`
    (prefill always keeps the XLA read path — the fused kernel is a
    decode-hot-path optimization)."""
    if chunk < 1:
        raise ValueError(f"prefill chunk must be >= 1, got {chunk}")
    _check_tp(spec, cfg)

    def build():
        if cfg.tp == 1:
            @jax.jit
            def step(params, pool, digests, tokens, start, n_valid,
                     page_row):
                idx = jnp.arange(chunk, dtype=jnp.int32)
                positions = (start + idx)[None]        # (1, C)
                valid = (idx < n_valid)[None]
                pids, offs = _page_targets(positions, page_row[None],
                                           valid, cfg)
                # newest LIVE position: the last VALID chunk lane (pad
                # lanes have positions past it but write only to the
                # trash page)
                last_pos = (start + n_valid - 1)[None]
                logits, pool2, digests2, bad = _forward(
                    params, tokens[None], positions, last_pos, pool,
                    digests, page_row[None], pids, offs, spec, cfg)
                last = jnp.clip(n_valid - 1, 0, chunk - 1)
                return pool2, digests2, logits[0, last], bad

            return step

        mesh = _serve_mesh(cfg.tp)
        sv = cfg.shard_view()
        qkv_fn = lambda blk, h: _shard_qkv(blk, h, spec, cfg.tp)  # noqa: E731
        merge_fn = lambda attn: _gather_heads(attn, cfg)          # noqa: E731

        def body(params, pool, digests, tokens, start, n_valid,
                 page_row):
            pool = pool[:, :, 0]
            digests = digests[:, :, 0]
            idx = jnp.arange(chunk, dtype=jnp.int32)
            positions = (start + idx)[None]
            valid = (idx < n_valid)[None]
            pids, offs = _page_targets(positions, page_row[None], valid,
                                       cfg)
            last_pos = (start + n_valid - 1)[None]
            logits, pool, digests, bad = _forward(
                params, tokens[None], positions, last_pos, pool,
                digests, page_row[None], pids, offs, spec, sv,
                qkv_fn=qkv_fn, merge_fn=merge_fn)
            bad = lax.psum(bad, AXIS_TENSOR)
            last = jnp.clip(n_valid - 1, 0, chunk - 1)
            return (pool[:, :, None], digests[:, :, None],
                    logits[0, last], bad)

        shard = P(None, None, AXIS_TENSOR)
        return jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P(), shard, shard, P(), P(), P(), P()),
            out_specs=(shard, shard, P(), P()), check_vma=False))

    return _STEP_CACHE.get_or_create(("prefill", spec, cfg, chunk), build)


def _ir_abstract_params(spec: ModelSpec):
    """ShapeDtypeStruct param pytree matching `_forward`'s layout (GQA
    form) — lets the IR analyzer trace the serving programs with no
    weights materialized."""
    d, ff, hd = spec.d_model, spec.d_ff, spec.head_dim

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    ln = lambda: {"scale": f32(d), "bias": f32(d)}  # noqa: E731
    blk = {"ln1": ln(), "ln2": ln(),
           "wq": {"kernel": f32(d, spec.n_heads * hd)},
           "wkv": {"kernel": f32(d, spec.kv_heads * 2 * hd)},
           "wo": {"kernel": f32(d, d)},
           "wi": {"kernel": f32(d, ff)},
           "wo_mlp": {"kernel": f32(ff, d)}}
    params = {"embed": {"embedding": f32(spec.vocab_size, d)},
              "ln_f": ln()}
    for i in range(spec.n_layers):
        params[f"block{i}"] = blk
    return params


def ir_programs(reg):
    """Program-contract declarations (analysis/ir/registry.py): the
    serving decode/prefill programs are bitwise-gated — prefill writes
    pages one PROGRAM, decode and corruption-repair read them from
    OTHERS, and the (8,23) decode additionally claims bitwise parity
    with the fp32-cache oracle — exactly the cross-program contract an
    ulp-unstable transcendental (the PR 12 exp2 class) breaks."""
    S, MP, CHUNK = 4, 4, 4
    spec = ModelSpec(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                     n_kv_heads=2, d_ff=64)
    deps = ("cpd_tpu.serve.model", "cpd_tpu.serve.kvcache",
            "cpd_tpu.quant.numerics")

    def _cfg(block=None, fmt=(4, 3), tp=1):
        return KVCacheConfig(n_layers=spec.n_layers, n_pages=8,
                             page_size=4, n_kv_heads=spec.kv_heads,
                             head_dim=spec.head_dim, exp_bits=fmt[0],
                             man_bits=fmt[1],
                             block_scale=block is not None,
                             block_size=block if block is not None
                             else 32, tp=tp)

    def _decode(block=None, fmt=(4, 3), tp=1):
        def build():
            cfg = _cfg(block, fmt, tp)
            step = make_decode_step(spec, cfg)
            i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
            args = (_ir_abstract_params(spec),
                    jax.ShapeDtypeStruct(cfg.pool_shape, jnp.uint8),
                    jax.ShapeDtypeStruct(cfg.digests_shape,
                                         jnp.uint32),
                    i32(S), i32(S), i32(S, MP),
                    jax.ShapeDtypeStruct((S,), jnp.bool_))
            return step, args
        return build

    def _prefill(fmt=(4, 3), tp=1):
        def build():
            cfg = _cfg(fmt=fmt, tp=tp)
            step = make_prefill_step(spec, cfg, CHUNK)
            i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
            args = (_ir_abstract_params(spec),
                    jax.ShapeDtypeStruct(cfg.pool_shape, jnp.uint8),
                    jax.ShapeDtypeStruct(cfg.digests_shape,
                                         jnp.uint32),
                    i32(CHUNK), i32(), i32(), i32(MP))
            return step, args
        return build

    def _tp_wire(n_tokens, fmt):
        # analytic cross-shard bytes (per device): one quantized
        # all_gather of the per-shard attention outputs per layer —
        # `gather_transport_bytes` is the same price the training ring
        # quotes, so serving and training share one wire ledger.
        h_loc = spec.n_heads // 2
        n = n_tokens * h_loc * spec.head_dim
        return lambda: spec.n_layers * gather_transport_bytes(
            n, 2, fmt[0], fmt[1], compressed=True)

    reg.declare("serve.decode[e4m3]", _decode(), deps=deps,
                bitwise=True)
    reg.declare("serve.decode[blocked-e4m3,b32]", _decode(block=32),
                deps=deps, bitwise=True)
    reg.declare("serve.decode[e8m23]", _decode(fmt=(8, 23)),
                deps=deps, bitwise=True)
    reg.declare("serve.prefill[e4m3]", _prefill(), deps=deps,
                bitwise=True)
    # tp=2 sharded twins (ISSUE 18): same contracts lifted onto the
    # head-group mesh — the cross-shard attention gather is the ONLY
    # wire, priced analytically and bitwise-gated like the ring.
    reg.declare("serve.decode[tp2,e4m3]", _decode(tp=2), deps=deps,
                axis_sizes={"tp": 2}, wire=_tp_wire(S, (4, 3)),
                bitwise=True)
    reg.declare("serve.decode[tp2,blocked-e4m3,b32]",
                _decode(block=32, tp=2), deps=deps,
                axis_sizes={"tp": 2}, wire=_tp_wire(S, (4, 3)),
                bitwise=True)
    reg.declare("serve.decode[tp2,e8m23]", _decode(fmt=(8, 23), tp=2),
                deps=deps, axis_sizes={"tp": 2},
                wire=_tp_wire(S, (8, 23)), bitwise=True)
    reg.declare("serve.prefill[tp2,e4m3]", _prefill(tp=2), deps=deps,
                axis_sizes={"tp": 2}, wire=_tp_wire(CHUNK, (4, 3)),
                bitwise=True)
