"""Hybrid short-convolution / attention mixture-of-experts LM (the LFM2 MoE
block, `model_type` lfm2_moe: LFM2-8B-A1B, LFM2-24B-A2B,
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json), as
ONE chip of an expert-parallel group runs it.

With N1, N2 RMSNorms of their own scale, every layer l is

    h = x + M_l(N1(x));   out = h + F_l(N2(h))

and M_l the mixer that `layer_types[l]` names:

* "conv", `ShortConv`, a gated depthwise causal convolution:
  [B | C | x~] = W_in u (d -> 3d, split in that order);  z = B * x~;
  v_t = w_0 z_{t-2} + w_1 z_{t-1} + w_2 z_t  (`l_cache` = 3 taps a
  channel, z = 0 before a sequence's first token);  y = W_out (C * v);
* "full_attention", `NormedGQA`: q = RMSNorm_D(u W_q) and
  k = RMSNorm_D(u W_k) a head at a time, each norm with a learned scale
  of the head width D; rotary positions after the norm (pairs
  (i, i + D/2)); causal softmax at 1/sqrt(D), groups of query heads
  sharing a key head; W_o.

F_l is a SiLU-gated MLP (`GatedMLP`) in the first `first_dense` layers
and this chip's share of the routed experts after them (`RoutedExperts`:
sigmoid scores, the top k of score + a selection bias that gets no
gradient, gates renormalised over the selected and scaled), with no
shared expert.  The model: embedding, the layers, a float32 final
RMSNorm, and the head tied to the embedding (`embed.attend` in float32),
over the rows of the vocabulary this chip holds.

`RMSNorm`, `GatedMLP`, `RoutedExperts`, `_rope` and `causal_attention`
are `models/mla_moe.py`'s.  The convolution is three shifted
multiply-adds along T, padded at each sequence's start and never across
the batch, with the taps' products summed in float32; XLA fuses it.

Counters (`step_counters`, merged by `train/lm.py`): the routed layers'
`moe_pairs_held`, `moe_load_max_over_mean` and `moe_compact`, as
`MLAMoELM` declares them.  Parameter tree, top level: `embed`,
`block0`..`block{L-1}`, `norm_f`; no leaf is one `megatron_shard_kind`
takes for a tensor-parallel one.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..obs import scopes
from ..ops.flash_gqa import KEEP_FLASH_RESIDUALS
from .mla_moe import (GatedMLP, RMSNorm, RoutedExperts, _dense, _init, _rope,
                      causal_attention)

__all__ = ["MIXERS", "ShortConv", "NormedGQA", "ConvMoEBlock", "ConvMoELM",
           "conv_moe_lm"]

MIXERS = ("conv", "full_attention")     # what a `layer_types` entry may be


class ShortConv(nn.Module):
    """Gated depthwise causal convolution, (B, T, d) -> (B, T, d)."""
    l_cache: int = 3            # taps a channel
    dtype: Any = jnp.float32
    init_std: float = 0.02

    @nn.compact
    def __call__(self, u):
        t, d = u.shape[1], u.shape[2]
        gate_b, gate_c, xt = jnp.split(_dense(self, 3 * d, "in_proj")(u), 3,
                                       -1)
        taps = self.param("taps", _init(self.init_std), (self.l_cache, d),
                          jnp.float32)
        # tap j meets z_{t - (l_cache - 1 - j)}: the pad is each
        # sequence's own, so no token sees another sequence's
        z = jnp.pad(gate_b * xt, ((0, 0), (self.l_cache - 1, 0), (0, 0)))
        v = sum(taps[j] * z[:, j:j + t].astype(jnp.float32)
                for j in range(self.l_cache))
        return _dense(self, d, "out_proj")(gate_c * v.astype(self.dtype))


class NormedGQA(nn.Module):
    """Grouped-query attention with every q and k head RMS-normed before
    the rotary positions, (B, T, d) -> (B, T, d)."""
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    eps: float = 1e-5
    attn_impl: str = "xla"      # "xla" | "chunked" | "flash"
    dtype: Any = jnp.float32
    init_std: float = 0.02

    @nn.compact
    def __call__(self, u, positions):
        b, t, d = u.shape
        width = self.head_dim

        def heads(name, n):
            return _dense(self, n * width, name)(u).reshape(b, t, n, width)

        q = _rope(RMSNorm(self.eps, self.dtype, name="q_norm")(
            heads("q_proj", self.n_heads)), positions, self.rope_theta)
        k = _rope(RMSNorm(self.eps, self.dtype, name="k_norm")(
            heads("k_proj", self.n_kv_heads)), positions, self.rope_theta)
        a = causal_attention(q, k, heads("v_proj", self.n_kv_heads),
                             self.attn_impl)
        return _dense(self, d, "out_proj")(
            a.reshape(b, t, self.n_heads * width))


class ConvMoEBlock(nn.Module):
    """x + M(N1(x)), then + F(N2(.)): M the mixer `mixer` names, F a dense
    gated MLP (`routed=False`) or this chip's routed experts."""
    mixer: str
    routed: bool
    n_heads: int
    n_kv_heads: int
    head_dim: int
    l_cache: int
    rope_theta: float
    d_ff: int           # the dense MLP's width
    n_experts: int
    experts_held: int
    expert_first: int
    top_k: int
    moe_d_ff: int
    routed_scaling: float
    eps: float = 1e-5
    attn_impl: str = "xla"
    dtype: Any = jnp.float32
    init_std: float = 0.02

    @nn.compact
    def __call__(self, x, positions):
        norm = lambda name: RMSNorm(self.eps, self.dtype, name=name)
        if self.mixer == "conv":
            with jax.named_scope(scopes.CONV_MIXER):
                x = x + ShortConv(self.l_cache, self.dtype, self.init_std,
                                  name="conv")(norm("norm1")(x))
        elif self.mixer == "full_attention":
            with jax.named_scope(scopes.GQA_ATTN):
                x = x + NormedGQA(
                    self.n_heads, self.n_kv_heads, self.head_dim,
                    self.rope_theta, self.eps, self.attn_impl, self.dtype,
                    self.init_std, name="attn")(norm("norm1")(x), positions)
        else:
            raise ValueError(f"unknown mixer {self.mixer!r}; expected one "
                             f"of {MIXERS}")
        h = norm("norm2")(x)
        if not self.routed:
            with jax.named_scope(scopes.DENSE_MLP):
                return x + GatedMLP(self.d_ff, self.dtype, self.init_std,
                                    name="mlp")(h)
        return x + RoutedExperts(
            self.n_experts, self.experts_held, self.expert_first,
            self.top_k, self.moe_d_ff, self.routed_scaling, self.dtype,
            self.init_std, name="moe")(h)


class ConvMoELM(nn.Module):
    """Decoder-only LM.  (B, T) int32 tokens -> (B, T, vocab) fp32 logits
    over the rows of the vocabulary this chip holds (`vocab_size`)."""
    vocab_size: int = 32000
    d_model: int = 512
    layer_types: Sequence[str] = ("conv", "full_attention")
    n_heads: int = 8
    n_kv_heads: int = 2
    d_ff: int = 2048                # the leading dense layers' MLP width
    first_dense: int = 1            # leading layers with a dense MLP
    l_cache: int = 3
    rope_theta: float = 10000.0
    eps: float = 1e-5
    n_experts: int = 8
    experts_held: Optional[int] = None      # None: every expert
    expert_first: int = 0
    top_k: int = 2
    moe_d_ff: int = 256
    routed_scaling: float = 1.0
    init_std: float = 0.02
    remat: bool = False             # jax.checkpoint each block but
                                    # its flash kernel's results
    attn_impl: str = "xla"
    dtype: Any = jnp.float32

    # name -> how `train/lm.py` merges the counter over layers, micro-
    # batches and data ranks before it reports it in the step's metrics
    step_counters = {"moe_pairs_held": "sum",
                     "moe_load_max_over_mean": "max",
                     "moe_compact": "mean"}

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        del train                   # no dropout: the family trains without
        positions = jnp.arange(tokens.shape[1])
        # float32 rows: the lookup is exact, and the tied head's product
        # is a float32 one
        embed = nn.Embed(self.vocab_size, self.d_model, dtype=jnp.float32,
                         embedding_init=_init(self.init_std), name="embed")
        x = embed(tokens).astype(self.dtype)
        block_cls = (nn.remat(ConvMoEBlock, policy=KEEP_FLASH_RESIDUALS)
                     if self.remat else ConvMoEBlock)
        kw = dict(
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.d_model // self.n_heads,
            l_cache=self.l_cache, rope_theta=self.rope_theta, d_ff=self.d_ff,
            n_experts=self.n_experts,
            experts_held=(self.n_experts if self.experts_held is None
                          else self.experts_held),
            expert_first=self.expert_first, top_k=self.top_k,
            moe_d_ff=self.moe_d_ff, routed_scaling=self.routed_scaling,
            eps=self.eps, attn_impl=self.attn_impl, dtype=self.dtype,
            init_std=self.init_std)
        for i, mixer in enumerate(self.layer_types):
            x = block_cls(mixer=mixer, routed=i >= self.first_dense, **kw,
                          name=f"block{i}")(x, positions)
        return embed.attend(RMSNorm(self.eps, jnp.float32, name="norm_f")(x))


def conv_moe_lm(vocab_size: int = 32000, d_model: int = 512,
                n_layers: int = 2, n_heads: int = 8,
                d_ff: Optional[int] = None, dtype=jnp.float32,
                n_kv_heads: Optional[int] = None,
                layer_types: Sequence[str] = ("conv", "full_attention"),
                **kw) -> ConvMoELM:
    """`layer_types` names each layer's mixer (one of `MIXERS`), so it has
    `n_layers` entries."""
    layer_types = tuple(layer_types)
    if len(layer_types) != n_layers:
        raise ValueError(f"{len(layer_types)} layer_types for {n_layers} "
                         f"layers")
    return ConvMoELM(vocab_size=vocab_size, d_model=d_model,
                     layer_types=layer_types, n_heads=n_heads,
                     n_kv_heads=n_kv_heads or n_heads,
                     d_ff=d_ff or 4 * d_model, dtype=dtype, **kw)
