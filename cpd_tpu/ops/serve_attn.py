"""Fused gather→unpack→attention Pallas kernel for the paged eXmY KV
cache — the serving hot path as ONE pass (ISSUE 18 tentpole, leg b).

The XLA decode path reads the cache in three materialized stages per
layer: page-row gather (``pool[layer][page_rows]``), eXmY unpack
(`kvcache.unpack_kv` — incl. the blocked sidecar), then the masked GQA
contraction (`serve.model._paged_attention`).  Each stage round-trips
the whole (S, max_pages · page_size, H_kv, D) capacity window through
HBM.  This kernel runs all three inside one `pallas_call`, and — the
`digest_rows_pallas` precedent (PR 12) — emits the per-gathered-page
Fletcher digest as a SECOND output of the same pass, so the read-path
integrity check costs no extra traversal of the page bytes.

Bitwise contract: the kernel body calls the EXACT unpack and attention
functions the XLA composition uses — they arrive as closures
(``unpack_fn`` / ``attend_fn``) from `serve/model.py`, so there is one
implementation, not a copy that can drift — and the digest is
`parallel.integrity.wire_digest` itself.  tests/test_serve_tp.py gates
kernel == XLA bitwise in interpret mode over GQA page shapes including
odd tail pages × odd blocks.

**Does not compile on TPU** (v5e, libtpu 0.0.34; `tools/pallas_check.py`
check 8): Mosaic has no lowering for the `dynamic_slice` that
`dynamic_index_in_dim` over the loaded pool becomes.  A kernel that
does — a grid over (slot, page) with the page table as a scalar-prefetch
operand and per-page BlockSpecs, instead of a whole-pool VMEM load —
is a rebuild, not a repair (ROADMAP S5/D3).  `make_decode_step(fused=
True)` therefore raises on TPU; the kernel runs only in the CPU
interpreter.

Composition with tensor parallelism: the caller hands in a SHARD-LOCAL
pool slice (legacy tp=1 layout) with the shard-view config's unpack
closure — the kernel is shard-oblivious, exactly like every other
kvcache function.

The fp32 oracle cache (``raw=True``) keeps the XLA path: fusing a
no-codec gather buys nothing and the oracle must stay the reference,
so `make_decode_step` rejects ``fused`` + ``raw``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..compat import pallas as pl  # noqa: F401  (kernel home: ..compat)
from ..obs import scopes
from ..parallel.integrity import wire_digest

__all__ = ["fused_gather_attention"]


def fused_gather_attention(pool_layer: jnp.ndarray,
                           q: jnp.ndarray,
                           page_rows: jnp.ndarray,
                           positions: jnp.ndarray,
                           last_pos: jnp.ndarray,
                           *, page_size: int,
                           unpack_fn, attend_fn,
                           interpret: bool = False) -> tuple:
    """One decode batch's paged attention in a single Pallas pass.

    pool_layer: ONE layer's page pool slice — (n_pages, 2, page_size,
    H_kv, D, WB) uint8 packed, or (n_pages, 2, page_size, row_bytes)
    blocked; q: (S, T, H, D) fp32 queries (T == 1 on the decode path);
    page_rows: (S, max_pages) int32 trash-padded page tables;
    positions: (S, T) int32 query positions; last_pos: (S,) newest live
    position per slot.

    ``unpack_fn``: gathered (S, MP, 2, page, ...) wire bytes ->
    (S, MP, 2, page, H_kv, D) fp32 — `kvcache.unpack_kv` under the
    caller's config.  ``attend_fn``: the masked GQA contraction —
    `serve.model._paged_attention`.

    Returns ``(attn, page_digests)``: attn (S, T, H, D) fp32 — bitwise
    what the XLA composition produces — and page_digests (S, max_pages)
    uint32, `wire_digest` of every gathered page's bytes as READ, for
    the engine's read-path integrity verdict."""
    s_count, max_pages = page_rows.shape
    t = q.shape[1]
    h, d = q.shape[2], q.shape[3]

    kernel = functools.partial(
        _fused_kernel, s_count=s_count, max_pages=max_pages,
        page_size=page_size, unpack_fn=unpack_fn, attend_fn=attend_fn)
    call = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((s_count, t, h, d), jnp.float32),
            jax.ShapeDtypeStruct((s_count, max_pages), jnp.uint32),
        ),
        interpret=interpret,
        name=scopes.kernel_name(scopes.KERNEL_FUSED_GATHER_ATTENTION),
    )
    with jax.named_scope(scopes.KERNEL_FUSED_GATHER_ATTENTION):
        return call(pool_layer, page_rows, q, positions, last_pos)


def _fused_kernel(pool_ref, rows_ref, q_ref, pos_ref, last_ref,
                  attn_ref, dig_ref, *, s_count: int, max_pages: int,
                  page_size: int, unpack_fn, attend_fn):
    """Kernel body: static (slot, page) gather loop, digest, unpack,
    attend — one traversal of the gathered bytes."""
    pool = pool_ref[:]
    rows = rows_ref[:]
    # page-row gather: the (S, MP) loop is static (jit-stable shapes);
    # each row index is a traced scalar from the page table
    kv = jnp.stack([
        jnp.stack([lax.dynamic_index_in_dim(pool, rows[s, p], axis=0,
                                            keepdims=False)
                   for p in range(max_pages)])
        for s in range(s_count)])            # (S, MP, 2, page, ...)
    # the read-path digest rides the pass: hash the bytes AS GATHERED,
    # before any decode touches them — what the engine compares against
    # the stored per-page digests
    dig_ref[:] = jax.vmap(jax.vmap(wire_digest))(kv)
    un = unpack_fn(kv)                       # (S, MP, 2, page, H, D)
    t_cap = max_pages * page_size
    hkv, hd = un.shape[-2], un.shape[-1]
    k = un[:, :, 0].reshape(s_count, t_cap, hkv, hd)
    v = un[:, :, 1].reshape(s_count, t_cap, hkv, hd)
    attn_ref[:] = attend_fn(q_ref[:], k, v, pos_ref[:], last_ref[:])
