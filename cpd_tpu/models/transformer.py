"""Transformer LM with tensor- and sequence-parallelism built in.

New capability beyond the reference (whose workloads are CNNs only,
SURVEY.md §5): a decoder-only LM whose forward pass is written to run
unchanged in two regimes —

* single device (``tp_axis=None, sp_axis=None``): plain local attention;
* inside ``shard_map`` over a ("dp","sp","tp") mesh: Megatron-style tensor
  parallelism (qkv/wi column-sharded, wo row-sharded, one `psum` over tp
  per projection pair) and sequence parallelism over the sp axis —
  ``sp_mode="ring"`` (K/V rotate via ppermute) or ``"ulysses"``
  (all_to_all heads<->sequence); both in ops/attention.py.

TPU-first choices: RoPE positions are computed from the sp rank's global
offset (no position-embedding table to shard); all Dense layers are
bias-free so the tp `psum` needs no bias correction; head count and ff
width are derived from the *runtime kernel shapes*, so the same module
code handles full (init-time) and per-rank (apply-time, shard_map-sliced)
parameter shapes.

`lm_param_specs` maps a param pytree to PartitionSpecs (the tp sharding
rules); train/lm.py consumes it for the whole-step shard_map.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.attention import (grouped_query_attention, local_attention,
                             ring_attention, ulysses_attention)
from ..ops.flash_gqa import KEEP_FLASH_RESIDUALS

__all__ = ["TransformerLM", "transformer_lm", "lm_param_specs"]


def _rope(x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """Rotary embedding on (B, T, H, D) with (T,) global positions."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (jnp.log(10000.0) / half))
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # (T, half)
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


class Block(nn.Module):
    head_dim: int
    d_ff: int           # GLOBAL ff width; local = d_ff // tp_size
    d_model: int
    tp_axis: Optional[str]
    sp_axis: Optional[str]
    tp_size: int        # 1 at init (global shapes); the mesh's tp size when
                        # applied inside shard_map (flax validates declared
                        # vs stored shapes, so features must be local)
    dtype: Any
    sp_mode: str = "ring"   # "ring" (ppermute K/V) | "ulysses" (all_to_all
                            # heads<->sequence; local heads % sp size == 0)
    decode: bool = False    # KV-cache autoregressive mode (single device)
    mlp: Optional[Any] = None   # factory () -> nn.Module replacing the
                                # dense pair (e.g. MoE experts); a custom
                                # mlp owns its own collectives — Block's tp
                                # psum applies only to the built-in pair
    scan_pair: bool = False     # return (x, None) — the (carry, out)
                                # shape nn.scan's body contract requires
    n_kv_heads: Optional[int] = None    # GQA: fewer K/V heads than query
                                        # heads (None = MHA, wqkv layout)
    dropout_rate: float = 0.0   # residual-branch dropout (after the attn
                                # and mlp projections, post-tp-psum so the
                                # mask applies to the full summed value —
                                # every tp rank must draw the SAME mask,
                                # which the stepper ensures by NOT folding
                                # the tp index into the rng)
    deterministic: bool = True  # False during training (LM threads its
                                # train flag here)
    ffn_exp: int = 8            # eXmY-accumulator GEMMs for the MLP pair
    ffn_man: int = 23           # (wi/wo_mlp) when != (8, 23): the
                                # reference's quantized forward/backward
                                # recipe (quant_module.py:30-52) composed
                                # into the LM.  Param layout stays Dense-
                                # compatible (QuantDense), so checkpoints
                                # and tp specs are unchanged.
    ffn_mode: str = "faithful"
    causal: bool = True         # False = bidirectional attention (ViT
                                # encoder use, models/vit.py); decode and
                                # sp paths require causal
    attn_impl: str = "xla"      # "flash" = Pallas TPU flash-attention
                                # kernel for the non-decode single-
                                # sequence path (O(T) memory; MHA only);
                                # hardware-validated by
                                # tools/pallas_check.py.  "chunked" =
                                # pure-XLA online-softmax K/V-block scan
                                # (flash's memory shape, any backend,
                                # GQA-native; ops/attention.py)

    def _psum_tp(self, x):
        return lax.psum(x, self.tp_axis) if self.tp_axis else x

    def _cached_attention(self, q, k, v, positions):
        """KV-cache attention (decode=True).

        The cache is created on the FIRST call (flax init) with this
        call's (B, T, H, D) shapes — so initialize with a dummy input of
        the MAXIMUM sequence length.  Every later call writes its k/v
        block at ``positions[0]`` and attends q over the whole cache with
        the mask ``key_pos <= query_pos`` — one code path serves both
        one-pass prefill (T = prompt length) and single-token decode
        (T = 1).

        OVERFLOW CONTRACT: writing past the allocated cache length cannot
        raise from inside jit (positions are traced values), so the layer
        poisons the ENTIRE output block with NaN instead — argmax/sampling
        over NaN logits would otherwise silently emit token 0.  `generate()`
        sizes the cache so this never triggers there; callers driving
        ``decode=True`` with their own cache management must either respect
        ``prompt_len + steps <= cache length`` or check outputs for NaN
        (``jnp.isnan(logits).any()``) after a step that might overflow
        (ADVICE r2)."""
        is_init = self.has_variable("cache", "cached_k")
        cache_k = self.variable("cache", "cached_k", jnp.zeros, k.shape,
                                k.dtype)
        cache_v = self.variable("cache", "cached_v", jnp.zeros, v.shape,
                                v.dtype)
        if not is_init:
            # init trace: caches get their (B, T_max, H_kv, D) zero
            # shapes; run plain causal attention so init outputs are
            # well-formed (grouped handles GQA head counts)
            return grouped_query_attention(q, k, v, causal=True)
        start = positions[0]
        cache_k.value = lax.dynamic_update_slice(
            cache_k.value, k.astype(cache_k.value.dtype), (0, start, 0, 0))
        cache_v.value = lax.dynamic_update_slice(
            cache_v.value, v.astype(cache_v.value.dtype), (0, start, 0, 0))
        # keys sit at global positions 0..T_max-1, queries at `positions`;
        # the q_offset mask (q_off+i >= ki) is exactly key_pos <=
        # query_pos, and also hides the unwritten cache tail.  GQA caches
        # the UNEXPANDED kv heads and the grouped kernel contracts
        # against them directly — no rep× expansion is ever materialized
        # (that would negate the cache-memory win; see ops/attention.py).
        out = grouped_query_attention(q, cache_k.value, cache_v.value,
                                      causal=True, q_offset=start)
        # capacity guard: past the allocated length dynamic_update_slice
        # silently clamps the write (corrupting the last slot), so poison
        # the output with NaN to fail loudly instead
        t_max = cache_k.value.shape[1]
        return jnp.where(positions[-1] < t_max, out, jnp.nan)

    @nn.compact
    def __call__(self, x, positions):
        # ---- attention ----
        h = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        if self.n_kv_heads is None:
            # MHA: fused projection.  Layout is HEAD-major — (n_heads, 3,
            # head_dim) in the feature dim — so a tp column-slice keeps
            # whole heads with their q,k,v together; local head count
            # comes from the runtime kernel shape.
            qkv = nn.Dense(3 * self.d_model // self.tp_size,
                           use_bias=False, dtype=self.dtype,
                           name="wqkv")(h)
            n_local = qkv.shape[-1] // (3 * self.head_dim)
            qkv = qkv.reshape(*qkv.shape[:-1], n_local, 3, self.head_dim)
            q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        else:
            # GQA: separate q and kv projections (fewer kv heads), both
            # head-major so tp column slices keep whole heads
            qp = nn.Dense(self.d_model // self.tp_size, use_bias=False,
                          dtype=self.dtype, name="wq")(h)
            kvp = nn.Dense(
                2 * self.n_kv_heads * self.head_dim // self.tp_size,
                use_bias=False, dtype=self.dtype, name="wkv")(h)
            n_local = qp.shape[-1] // self.head_dim
            nkv_local = kvp.shape[-1] // (2 * self.head_dim)
            if n_local % nkv_local:
                raise ValueError(
                    f"n_heads ({n_local} local) must be a multiple of "
                    f"n_kv_heads ({nkv_local} local)")
            q = qp.reshape(*qp.shape[:-1], n_local, self.head_dim)
            kvp = kvp.reshape(*kvp.shape[:-1], nkv_local, 2, self.head_dim)
            k, v = kvp[..., 0, :], kvp[..., 1, :]
        q = _rope(q, positions)
        k = _rope(k, positions)
        if self.sp_mode not in ("ring", "ulysses"):
            raise ValueError(f"unknown sp_mode {self.sp_mode!r}; "
                             "expected 'ring' or 'ulysses'")
        if not self.causal and (self.decode or self.sp_axis):
            raise ValueError("causal=False (bidirectional encoder) does "
                             "not compose with decode or sp paths")
        if self.attn_impl not in ("xla", "flash", "chunked"):
            raise ValueError(f"unknown attn_impl {self.attn_impl!r}; "
                             "expected 'xla', 'flash' or 'chunked'")
        if (self.attn_impl == "flash" and self.sp_axis
                and self.sp_mode == "ring"):
            raise ValueError("attn_impl='flash' does not compose with "
                             "ring sequence parallelism (the ring's "
                             "online-softmax accumulation is its own "
                             "schedule); use sp_mode='ulysses'")
        if self.decode:
            attn = self._cached_attention(q, k, v, positions)
        elif self.sp_axis:
            # sequence-parallel paths take UNEXPANDED GQA kv: the ring
            # rotates H_kv-headed blocks and ulysses all_to_alls them
            # (expanding internally only when H_kv doesn't divide the sp
            # size) — rep x fewer ICI bytes than expanding first
            # (ops/attention.py)
            if self.sp_mode == "ulysses":
                attn = ulysses_attention(q, k, v, self.sp_axis,
                                         causal=True, impl=self.attn_impl)
            else:
                # ring accepts impl='chunked' (inner sub-block fold, for
                # T_local >> block); 'flash' was rejected above
                attn = ring_attention(q, k, v, self.sp_axis, causal=True,
                                      impl=("chunked"
                                            if self.attn_impl == "chunked"
                                            else "xla"))
        else:
            attn = grouped_query_attention(q, k, v, causal=self.causal,
                                           impl=self.attn_impl)
        attn = attn.reshape(*attn.shape[:-2], n_local * self.head_dim)
        proj = nn.Dense(self.d_model, use_bias=False, dtype=self.dtype,
                        name="wo")(attn)
        x = x + self._dropout(self._psum_tp(proj))

        # ---- mlp ----
        h = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        if self.mlp is not None:
            out = x + self.mlp()(h)
        else:
            if (self.ffn_exp, self.ffn_man) != (8, 23):
                from ..quant.quant_module import QuantDense
                dense = partial(QuantDense, exp=self.ffn_exp,
                                man=self.ffn_man, mode=self.ffn_mode)
            else:
                dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
            h = dense(self.d_ff // self.tp_size, name="wi")(h)
            h = nn.gelu(h)
            h = dense(self.d_model, name="wo_mlp")(h)
            # psum BEFORE any downcast: the quant path's per-shard fp32
            # accumulator results must reduce in fp32 (QuantDense's
            # documented contract); the plain path's h is already dtype
            out = x + self._dropout(self._psum_tp(h).astype(x.dtype))
        return (out, None) if self.scan_pair else out

    def _dropout(self, x):
        if not self.dropout_rate:
            return x
        if not 0.0 < self.dropout_rate < 1.0:
            # 1.0 would silently zero every residual branch (flax returns
            # zeros_like at rate==1); out-of-range rates mis-scale
            raise ValueError(f"dropout_rate must be in [0, 1), got "
                             f"{self.dropout_rate}")
        return nn.Dropout(self.dropout_rate,
                          deterministic=self.deterministic)(x)


class TransformerLM(nn.Module):
    """Decoder-only LM.  Input: (B, T_local) int32 tokens; output:
    (B, T_local, vocab) fp32 logits."""
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None   # GQA; None = MHA
    dropout_rate: float = 0.0
    d_ff: int = 2048
    tp_axis: Optional[str] = None
    sp_axis: Optional[str] = None
    tp_size: int = 1
    sp_mode: str = "ring"
    decode: bool = False
    remat: bool = False     # jax.checkpoint each block: activations are
                            # recomputed in backward instead of stored —
                            # O(sqrt) activation memory for deep stacks,
                            # the standard TPU HBM<->FLOPs trade; the
                            # flash kernel's results are kept
                            # (`ops/flash_gqa.py:KEEP_FLASH_RESIDUALS`)
    scan_layers: bool = False   # ONE nn.scan'd block instead of a Python
                                # loop: layer body traced/compiled once
                                # regardless of depth; params gain a
                                # leading (n_layers,) axis (a different
                                # checkpoint layout — lm_param_specs is
                                # rank-aware for it)
    ffn_exp: int = 8        # quantized-accumulator MLP GEMMs when !=
    ffn_man: int = 23       # (8, 23) — see Block.ffn_exp
    ffn_mode: str = "faithful"
    attn_impl: str = "xla"  # "flash" = Pallas TPU kernel (see Block)
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        t_local = tokens.shape[1]
        if self.decode:
            if self.sp_axis or self.tp_axis:
                raise ValueError("decode=True (KV cache) is single-device; "
                                 "unset sp_axis/tp_axis")
            # running position: init at 0 when the cache is created, then
            # advance by this call's token count (prefill or one token)
            is_init = self.has_variable("cache", "position")
            pos_var = self.variable("cache", "position",
                                    lambda: jnp.zeros((), jnp.int32))
            offset = pos_var.value if is_init else 0
            if is_init:
                pos_var.value = pos_var.value + t_local
        elif self.sp_axis:
            offset = lax.axis_index(self.sp_axis) * t_local
        else:
            offset = 0
        positions = offset + jnp.arange(t_local)

        emb = nn.Embed(self.vocab_size, self.d_model,
                       dtype=self.dtype, param_dtype=self.param_dtype,
                       name="embed")
        x = emb(tokens)
        head_dim = self.d_model // self.n_heads
        # nn.remat wraps the module class so flax keeps param/cache
        # bookkeeping intact under jax.checkpoint; decode is cache-mutating
        # (no backward pass), so remat is train-path only.  Under nn.scan
        # the scan itself provides the staging checkpoint needs, so CSE
        # barriers are unnecessary (jax.checkpoint docs: prevent_cse=False
        # inside scan) — keeping them would wedge optimization-barrier ops
        # into the one scanned layer body.
        if self.remat and not self.decode:
            block_cls = nn.remat(Block, prevent_cse=not self.scan_layers,
                                 policy=KEEP_FLASH_RESIDUALS)
        else:
            block_cls = Block
        block_kw = dict(head_dim=head_dim, d_ff=self.d_ff,
                        d_model=self.d_model, tp_axis=self.tp_axis,
                        sp_axis=self.sp_axis, tp_size=self.tp_size,
                        dtype=self.dtype, sp_mode=self.sp_mode,
                        decode=self.decode, n_kv_heads=self.n_kv_heads,
                        dropout_rate=self.dropout_rate,
                        deterministic=not train, ffn_exp=self.ffn_exp,
                        ffn_man=self.ffn_man, ffn_mode=self.ffn_mode,
                        attn_impl=self.attn_impl)
        if self.scan_layers:
            if self.decode:
                raise ValueError("scan_layers does not compose with "
                                 "decode (per-layer caches need the "
                                 "unrolled blocks)")
            scan = nn.scan(block_cls, variable_axes={"params": 0},
                           # dropout must be listed or lift.pack filters
                           # the rng out of the scanned scope entirely
                           # (InvalidRngError at the first train step);
                           # True = a distinct mask per layer, matching
                           # the unrolled stack's per-block make_rng
                           split_rngs={"params": True, "dropout": True},
                           in_axes=nn.broadcast, length=self.n_layers)
            x, _ = scan(**block_kw, scan_pair=True, name="blocks")(
                x, positions)
        else:
            for i in range(self.n_layers):
                x = block_cls(**block_kw, name=f"block{i}")(x, positions)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        logits = emb.attend(x.astype(self.param_dtype))  # tied head
        return logits.astype(jnp.float32)


def transformer_lm(vocab_size: int = 32000, d_model: int = 512,
                   n_layers: int = 4, n_heads: int = 8,
                   d_ff: Optional[int] = None, dtype=jnp.float32,
                   **kw) -> TransformerLM:
    return TransformerLM(vocab_size=vocab_size, d_model=d_model,
                         n_layers=n_layers, n_heads=n_heads,
                         d_ff=d_ff or 4 * d_model, dtype=dtype, **kw)


def megatron_shard_kind(names) -> Optional[str]:
    """The Megatron rule for a param path (list of name strings):
    'col' = output dim tp-sharded (wqkv/wi kernels), 'row' = input dim
    tp-sharded (wo/wo_mlp kernels), None = replicated.  Exact layer-name
    matching (not substring): a future param whose path merely *contains*
    "wo" must not silently get row-sharded.  Shared by lm_param_specs and
    models/pipeline_lm.pp_param_specs."""
    if len(names) >= 2 and names[-1] == "kernel":
        if names[-2] in ("wqkv", "wq", "wkv", "wi"):
            return "col"
        if names[-2] in ("wo", "wo_mlp"):
            return "row"
    return None


def lm_param_specs(params, tp_axis: str = "tp"):
    """PartitionSpec pytree for the Megatron sharding rules: qkv and wi
    kernels column-sharded (out dim on tp), wo kernels row-sharded (in
    dim on tp), everything else replicated.  Rank-aware so the rules
    apply to both layouts — per-layer (in, out) kernels and the
    scan_layers stacked (n_layers, in, out) kernels (leading layer axis
    stays unsharded)."""

    def spec(path, leaf):
        kind = megatron_shard_kind([str(getattr(k, "key", k))
                                    for k in path])
        nd = jnp.ndim(leaf)
        if kind == "col":
            return P(*([None] * (nd - 1)), tp_axis)
        if kind == "row":
            return P(*([None] * (nd - 2)), tp_axis, None)
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)
