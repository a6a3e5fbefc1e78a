"""Latent-attention mixture-of-experts LM (the DeepSeek-V3 block: Moonlight,
DeepSeek-V2/V3), as ONE chip of an expert-parallel group runs it.

What `models/transformer.py` and the Switch-style `models/moe.py` do not
have, each a module here:

* `RMSNorm`, `GatedMLP` (SiLU-gated, three bias-free matrices);
* `LatentAttention` — multi-head latent attention: keys and values are
  expanded from one normalised low-rank latent per token; a q/k head is
  `qk_nope_dim + qk_rope_dim` wide, of which the rope part rotates and is
  ONE vector shared by every head on the key side; a v head has its own
  width.  The softmax scale is 1/sqrt(q/k width);
* `RoutedExperts` — sigmoid scores over `n_experts`, the `top_k` largest
  of score + selection bias, gates renormalised over the selected and
  scaled; **no token is dropped**.  The module is told which experts it
  holds (`experts_held` of them from `expert_first`): pairs routed to an
  absent expert add nothing here (their owners' chips add them; on one
  chip there is no exchange and none is stood in for).  Static shapes:
  the T·k (token, slot) pairs are sorted so that the held experts' pairs
  come first in expert order (index work, T·k long); the first `C` sorted
  rows are gathered into a buffer, pass three grouped products whose
  group sizes are the held experts' counts, are scaled by their gates and
  added back into their tokens.  `C` is twice the pairs the chip holds
  under an even router (`_row_bound`), and a step that holds more takes
  the same path over all T·k rows, the other branch of one `lax.cond` on
  the device: dropless whatever the routing, at the cost of the rows held
  when the routing is sane.  The grouped products (`ops/grouped.py`) cost
  what the live rows cost, and leave the rows past them unwritten
  (PERF.md §6, PR 30 and PR 35);
* `MLAMoELM` — `first_dense` leading blocks with a dense `GatedMLP`, the
  rest with `RoutedExperts` plus a shared expert; untied float32 head.

Counters: each `RoutedExperts` sows `moe_pairs_held`,
`moe_load_max_over_mean` and `moe_compact` (1 where the step ran over `C`
rows) into the `"counters"` collection; the model
declares how each is merged over layers (and ranks) in `step_counters`,
which `train/lm.py` reads.  Parameter leaves are named so that
`megatron_shard_kind` takes none of them for a tensor-parallel one.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..obs import scopes
from ..ops.attention import _chunked_attention, grouped_query_attention
from ..ops.flash_gqa import KEEP_FLASH_RESIDUALS
from ..ops.grouped import _TILE as ROW_TILE, grouped_matmul

__all__ = ["RMSNorm", "GatedMLP", "causal_attention", "LatentAttention",
           "RoutedExperts", "MLAMoEBlock", "MLAMoELM", "mla_moe_lm", "COUNTERS"]

COUNTERS = "counters"   # the flax collection the counters are sown into

# The `d`-wide rows of `RoutedExperts` are bounded by this many times the
# pairs a chip holds when the router is even.  The source balances its
# experts by the selection bias (the fullest expert a few percent over the
# mean in training; 1.45 here at seeded weights with nothing balancing, and
# that is one expert: the chip's 8 together read 12,293 a layer against
# 12,288, ledger, PR 34), so a chip whose share doubles is a step gone wrong,
# and that step is exact too: it runs over all T·k rows.  A constant with
# its reason, not a setting.
_HELD_ROWS_OVER_EXPECTED = 2


def _row_bound(pairs: int, held: int, n_experts: int) -> int:
    """`C`: rows of the `d`-wide buffer for `pairs` (token, slot) pairs on a
    chip that holds `held` of `n_experts`, whole row tiles of the grouped
    product; `pairs` itself (no bound below the dropless one) from a share
    of a half."""
    tiles = -(-_HELD_ROWS_OVER_EXPECTED * pairs * held
              // (n_experts * ROW_TILE))
    return min(pairs, tiles * ROW_TILE)


def _init(std: float):
    return nn.initializers.normal(stddev=std)


def _dense(module, features: int, name: str) -> nn.Dense:
    """A bias-free projection in `module`'s compute type and seeded as it
    says (`dtype`, `init_std`)."""
    return nn.Dense(features, use_bias=False, dtype=module.dtype,
                    kernel_init=_init(module.init_std), name=name)


def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """Rotary positions on (B, T, H, D); pairs are (i, i + D/2)."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(jnp.float32(theta)) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def causal_attention(q, k, v, impl: str):
    """Causal softmax attention of (B, T, H, D) queries against (B, T,
    H_kv, D) keys and (B, T, H_kv, Dv) values, H_kv dividing H (a group
    of query heads shares a key head), by the path `impl` names: "flash"
    (the Pallas kernels of ops/flash_gqa.py), "chunked" (the online-
    softmax scan) or "xla"."""
    if impl == "flash":
        from ..ops.flash_gqa import flash_gqa
        return flash_gqa(q, k, v, True)
    if impl == "chunked":
        one = lambda q, k, v: _chunked_attention(q, k, v, True, 0, 0)
        # a sequence at a time: the scan's backward keeps an output-
        # sized float32 carry for every block of keys, and the
        # sequences' carries need not live together (2 x 8,192 tokens
        # compiled for a v5e: 8.7 GiB of temporaries at once, 4.7 so)
        return one(q, k, v) if q.shape[0] == 1 else lax.map(
            lambda x: one(*(y[None] for y in x))[0], (q, k, v))
    if impl == "xla":
        return grouped_query_attention(q, k, v, causal=True)
    raise ValueError(f"unknown attn_impl {impl!r}; "
                     "expected 'xla', 'flash' or 'chunked'")


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                            + self.eps)
        return (y * scale).astype(self.dtype)


class GatedMLP(nn.Module):
    """(silu(x W_gate) * x W_up) W_down."""
    d_ff: int
    dtype: Any = jnp.float32
    init_std: float = 0.02

    @nn.compact
    def __call__(self, x):
        h = nn.silu(_dense(self, self.d_ff, "gate_proj")(x)) * _dense(
            self, self.d_ff, "up_proj")(x)
        return _dense(self, x.shape[-1], "down_proj")(h)


class LatentAttention(nn.Module):
    n_heads: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    eps: float = 1e-5
    attn_impl: str = "xla"      # "xla" | "chunked" | "flash" (the Pallas
                                # kernels of ops/flash_gqa.py)
    dtype: Any = jnp.float32
    init_std: float = 0.02

    @nn.compact
    def __call__(self, h, positions):
        b, t, d = h.shape
        nh, nope, rope = self.n_heads, self.qk_nope_dim, self.qk_rope_dim
        dense = lambda n, name: _dense(self, n, name)
        q = dense(nh * (nope + rope), "q_proj")(h).reshape(
            b, t, nh, nope + rope)
        c = dense(self.kv_lora_rank + rope, "kv_down")(h)
        c_kv, k_rope = c[..., :self.kv_lora_rank], c[..., self.kv_lora_rank:]
        kv = dense(nh * (nope + self.v_head_dim), "kv_up")(
            RMSNorm(self.eps, self.dtype, name="kv_norm")(c_kv)).reshape(
                b, t, nh, nope + self.v_head_dim)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], positions,
                                  self.rope_theta)], -1)
        k_rope = _rope(k_rope[:, :, None, :], positions, self.rope_theta)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, t, nh, rope))], -1)
        a = causal_attention(q, k, v, self.attn_impl)
        return dense(d, "out_proj")(a.reshape(b, t, nh * self.v_head_dim))


class RoutedExperts(nn.Module):
    """This chip's share of a top-k sigmoid-routed expert layer: the sum
    over the selected experts that are held here.  (B, T, d) -> (B, T, d)."""
    n_experts: int          # the router's width: every expert of the layer
    experts_held: int       # how many of them this chip holds ...
    expert_first: int       # ... starting from this id
    top_k: int
    d_ff: int
    routed_scaling: float = 1.0
    dtype: Any = jnp.float32
    init_std: float = 0.02

    @nn.compact
    def __call__(self, h):
        b, t, d = h.shape
        n, k, held = b * t, self.top_k, self.experts_held
        if not 0 <= self.expert_first <= self.n_experts - held:
            raise ValueError(
                f"experts {self.expert_first}..{self.expert_first + held} "
                f"are not among the layer's {self.n_experts}")
        x = h.reshape(n, d)
        w_r = self.param("router", _init(self.init_std),
                         (d, self.n_experts), jnp.float32)
        # the selection bias is not trained by the gradient (the source
        # steps it by the experts' load); it enters through stop_gradient
        bias = self.param("score_bias", nn.initializers.zeros,
                          (self.n_experts,), jnp.float32)
        expert = lambda name, shape: self.param(
            name, _init(self.init_std), (held, *shape), jnp.float32
        ).astype(self.dtype)
        w_gate = expert("experts_gate", (d, self.d_ff))
        w_up = expert("experts_up", (d, self.d_ff))
        w_down = expert("experts_down", (self.d_ff, d))

        with jax.named_scope(scopes.MOE_ROUTER):
            # float32 at full precision, as the source's gate: a token's
            # 6th and 7th scores can lie closer than bf16 resolves
            s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), w_r,
                                       precision=lax.Precision.HIGHEST))
            _, chosen = lax.top_k(s + lax.stop_gradient(bias), k)   # (n, k)
            picked = jnp.take_along_axis(s, chosen, 1)
            gates = self.routed_scaling * picked / (
                picked.sum(-1, keepdims=True) + 1e-20)

        with jax.named_scope(scopes.MOE_DISPATCH):
            # index work, over every pair whatever the routing
            local = chosen.reshape(-1) - self.expert_first       # (n*k,)
            here = (local >= 0) & (local < held)
            # held experts' pairs first, in expert order; absent last
            group = jnp.where(here, local, held)
            order = jnp.argsort(group, stable=True)
            sizes = jnp.sum(group[:, None] == jnp.arange(held)[None, :],
                            0, dtype=jnp.int32)
            pairs_held = sizes.sum()

        def over_rows(c, x, gates, w_gate, w_up, w_down):
            """The `d`-wide work on the first `c` sorted rows, (n, d)
            float32 out: every held pair's term when `pairs_held <= c`."""
            with jax.named_scope(scopes.MOE_DISPATCH):
                live = (jnp.arange(c) < pairs_held)[:, None]
                pair = order[:c]
                token = pair // k
                # a grouped product leaves the rows past the held count
                # as they were in memory, forward and backward
                # (ops/grouped.py): `where` keeps them out of the tokens'
                # gradient here and out of the output below
                rows = jnp.where(live, x[token], 0)             # (c, d)
                gate_of_row = gates.reshape(-1)[pair]
            with jax.named_scope(scopes.MOE_EXPERTS):
                act = nn.silu(grouped_matmul(rows, w_gate, sizes)) * (
                    grouped_matmul(rows, w_up, sizes))
                y = grouped_matmul(act, w_down, sizes)
                # masked BEFORE the gating product: its transpose
                # multiplies the gates' cotangent by y, and
                # 0 x (not finite) is not 0
                y = jnp.where(live, y, 0) * (
                    gate_of_row[:, None].astype(y.dtype))
            with jax.named_scope(scopes.MOE_COMBINE):
                if c < n * k:
                    # a token's at most k terms added where they lie (a
                    # dead row adds its zero): the cost follows c, and the
                    # transpose is a gather of c rows (v5e, 24,576 rows:
                    # 3.0 ms and 0.65; gathered back through the inverse
                    # permutation, clamped and masked, 3.1 and 11.5)
                    return jnp.zeros((n, d), jnp.float32).at[token].add(
                        y.astype(jnp.float32))
                back = jnp.zeros_like(order).at[order].set(
                    jnp.arange(n * k, dtype=order.dtype))
                return y[back].reshape(n, k, d).astype(jnp.float32).sum(1)

        c = _row_bound(n * k, held, self.n_experts)
        operands = (x, gates, w_gate, w_up, w_down)
        if c == n * k:
            compact = jnp.zeros((), jnp.float32)
            out = over_rows(c, *operands)
        else:
            # a `cond` hands its backward the residuals of BOTH branches,
            # the branch not taken writing zeros for its own (T·k rows,
            # `d` wide): under `jax.checkpoint` a branch saves its
            # operands and nothing else
            fits = pairs_held <= c
            compact = fits.astype(jnp.float32)
            out = lax.cond(
                fits, jax.checkpoint(functools.partial(over_rows, c)),
                jax.checkpoint(functools.partial(over_rows, n * k)),
                *operands)

        mean = pairs_held.astype(jnp.float32) / held
        self.sow(COUNTERS, "moe_pairs_held", pairs_held.astype(jnp.float32))
        self.sow(COUNTERS, "moe_load_max_over_mean",
                 jnp.where(mean > 0, sizes.max() / jnp.maximum(mean, 1e-9),
                           0.0))
        self.sow(COUNTERS, "moe_compact", compact)
        return out.astype(self.dtype).reshape(b, t, d)


class MLAMoEBlock(nn.Module):
    """x + Attn(norm(x)), then x + F(norm(x)): F a dense gated MLP
    (`routed=False`) or this chip's routed experts plus the shared one."""
    routed: bool
    n_heads: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    rope_theta: float
    d_ff: int           # the dense MLP's width
    n_experts: int
    experts_held: int
    expert_first: int
    top_k: int
    moe_d_ff: int
    shared_d_ff: int    # the shared expert's width (0: none)
    routed_scaling: float
    eps: float = 1e-5
    attn_impl: str = "xla"
    dtype: Any = jnp.float32
    init_std: float = 0.02

    @nn.compact
    def __call__(self, x, positions):
        norm = lambda name: RMSNorm(self.eps, self.dtype, name=name)
        with jax.named_scope(scopes.MLA):
            x = x + LatentAttention(
                self.n_heads, self.kv_lora_rank, self.qk_nope_dim,
                self.qk_rope_dim, self.v_head_dim, self.rope_theta,
                self.eps, self.attn_impl, self.dtype,
                self.init_std, name="attn")(norm("norm1")(x), positions)
        h = norm("norm2")(x)
        if not self.routed:
            with jax.named_scope(scopes.DENSE_MLP):
                return x + GatedMLP(self.d_ff, self.dtype, self.init_std,
                                    name="mlp")(h)
        out = RoutedExperts(self.n_experts, self.experts_held,
                            self.expert_first, self.top_k, self.moe_d_ff,
                            self.routed_scaling, self.dtype, self.init_std,
                            name="moe")(h)
        if self.shared_d_ff:
            with jax.named_scope(scopes.MOE_SHARED):
                out = out + GatedMLP(self.shared_d_ff, self.dtype,
                                     self.init_std, name="shared")(h)
        return x + out


class MLAMoELM(nn.Module):
    """Decoder-only LM.  (B, T) int32 tokens -> (B, T, vocab) fp32 logits
    over the rows of the vocabulary this chip holds (`vocab_size`)."""
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    d_ff: int = 2048                # the leading dense layers' MLP width
    first_dense: int = 1            # leading layers with a dense MLP
    kv_lora_rank: int = 128
    qk_nope_dim: int = 32
    qk_rope_dim: int = 16
    v_head_dim: int = 32
    rope_theta: float = 10000.0
    eps: float = 1e-5
    n_experts: int = 8
    experts_held: Optional[int] = None      # None: every expert
    expert_first: int = 0
    top_k: int = 2
    moe_d_ff: int = 256
    n_shared_experts: int = 1
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
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     embedding_init=_init(self.init_std),
                     name="embed")(tokens)
        block_cls = (nn.remat(MLAMoEBlock, policy=KEEP_FLASH_RESIDUALS)
                     if self.remat else MLAMoEBlock)
        held = (self.n_experts if self.experts_held is None
                else self.experts_held)
        kw = dict(
            n_heads=self.n_heads, kv_lora_rank=self.kv_lora_rank,
            qk_nope_dim=self.qk_nope_dim, qk_rope_dim=self.qk_rope_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
            d_ff=self.d_ff, n_experts=self.n_experts, experts_held=held,
            expert_first=self.expert_first, top_k=self.top_k,
            moe_d_ff=self.moe_d_ff,
            shared_d_ff=self.n_shared_experts * self.moe_d_ff,
            routed_scaling=self.routed_scaling, eps=self.eps,
            attn_impl=self.attn_impl, dtype=self.dtype,
            init_std=self.init_std)
        for i in range(self.n_layers):
            x = block_cls(routed=i >= self.first_dense, **kw,
                          name=f"block{i}")(x, positions)
        x = RMSNorm(self.eps, jnp.float32, name="norm_f")(x)
        return nn.Dense(self.vocab_size, use_bias=False, dtype=jnp.float32,
                        kernel_init=_init(self.init_std),
                        name="lm_head")(x)


def mla_moe_lm(vocab_size: int = 32000, d_model: int = 512,
               n_layers: int = 4, n_heads: int = 8,
               d_ff: Optional[int] = None, dtype=jnp.float32,
               n_kv_heads: Optional[int] = None, **kw) -> MLAMoELM:
    """`n_kv_heads` is accepted for the LM factories' common signature: in
    latent attention every head has keys and values of its own."""
    if n_kv_heads not in (None, n_heads):
        raise ValueError(f"latent attention has a key head for every "
                         f"query head: n_kv_heads {n_kv_heads} != {n_heads}")
    # the benchmark's key (benchmark/configs/moonlight_16b_a3b_ep8_d5.json,
    # whose files this repo's PRs may not edit), from when attention's
    # backward was chosen by the caller; dropped here, and this line goes
    # when a `benchmark` PR has taken the key out (ROADMAP D11)
    kw.pop("flash_bwd", None)
    return MLAMoELM(vocab_size=vocab_size, d_model=d_model,
                    n_layers=n_layers, n_heads=n_heads,
                    d_ff=d_ff or 4 * d_model, dtype=dtype, **kw)
