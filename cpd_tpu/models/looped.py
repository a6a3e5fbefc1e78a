"""Looped LM (Ouro; Zhu et al., "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741): ONE stack of layers applied
`n_loops` times to its own output with the same weights, an exit gate on
every pass's state, and the language-model loss at every exit weighted by
the exit distribution the gates define, less an entropy term.

With `N` an RMSNorm of its own scale, `h0 = E[tokens]`, `R = n_loops`:

    block_l(h):  a = h + N2_l(MHA_l(N1_l(h)));  a + N4_l(MLP_l(N3_l(a)))
    pass r:      h^r = N_f(block_L(... block_1(h^{r-1}) ...))
    exit r:      z^r = W_head h^r;  lam^r = sigmoid(w_g . h^r + b_g)
                 p^r = lam^r prod_{j<r}(1 - lam^j),  p^R = prod_{j<R}(1 - lam^j)
    loss/token:  sum_r p^r CE(z^r, target) - beta H(p)

`MHA` is bias-free rotary multi-head attention (pairs (i, i + D/2), a
configurable base), `MLP` the SiLU-gated `GatedMLP`; both, `RMSNorm` and
the rotary function are `models/mla_moe.py`'s.

What the loop asks of the program, and how it is met:

* the passes are a `nn.scan` over `r` with the parameters broadcast, so
  the compiled program holds the stack once and a weight's gradient is
  the sum over its `R` uses, which falls out of the scan's transpose (the
  float32 master weights are cast to the compute type inside the body, so
  that sum is carried in float32);
* each block is recomputed in the backward pass (`remat`), and each
  exit's head and cross-entropy too, always: `R` float32 logits tensors
  are never live together;
* the loss is not the cross-entropy of one logits tensor, so the model
  owns it: `token_losses(tokens, targets)` returns the per-token terms
  and `train/lm.py` asks for them.  `__call__` returns the last exit's
  logits (initialisation, evaluation, generation).

Counters (`step_counters`, merged by `train/lm.py`): `loop_expected_exit`,
the mean over tokens of `sum_r r p^r` (1.875 at `R` = 4 where every `lam`
is 0.5), and `loop_last_exit_mass`, the mean of `p^R` (0.125 there).
Parameter tree, top level: `embed`, `block0`..`block{L-1}`, `final_norm`,
`exit_gate`, `lm_head`; no leaf is one `megatron_shard_kind` takes for a
tensor-parallel one.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from ..obs import scopes
from ..ops.flash_gqa import KEEP_FLASH_RESIDUALS
from .mla_moe import (COUNTERS, GatedMLP, RMSNorm, _dense, _init, _rope,
                      causal_attention)

__all__ = ["LoopBlock", "LoopedLM", "looped_lm", "exit_distribution"]


def exit_distribution(gates: jnp.ndarray):
    """`(log p, p)` over the leading axis of `gates` (R, ...): `p^r` is the
    chance of leaving at exit r, `lam^r = sigmoid(gates[r])` for r < R and
    the last exit takes what is left.  In logarithms, so that a gate far
    from zero gives exact zeros and no NaN."""
    log_stay = jax.nn.log_sigmoid(-gates[:-1])          # log(1 - lam^j)
    stayed = jnp.concatenate([jnp.zeros_like(gates[:1]),
                              jnp.cumsum(log_stay, 0)])  # sum over j < r
    log_leave = jnp.concatenate([jax.nn.log_sigmoid(gates[:-1]),
                                 jnp.zeros_like(gates[:1])])
    log_p = stayed + log_leave
    return log_p, jnp.exp(log_p)


class LoopBlock(nn.Module):
    """A sandwich-normed block: the residual stream takes the NORMED
    output of attention and of the gated MLP."""
    n_heads: int
    d_ff: int
    rope_theta: float = 10000.0
    eps: float = 1e-6
    attn_impl: str = "xla"      # "xla" | "chunked" | "flash"
    dtype: Any = jnp.float32
    init_std: float = 0.02

    @nn.compact
    def __call__(self, h, positions):
        b, t, d = h.shape
        norm = lambda name: RMSNorm(self.eps, self.dtype, name=name)
        heads = lambda x: x.reshape(b, t, self.n_heads, d // self.n_heads)
        with jax.named_scope(scopes.LOOP_ATTN):
            u = norm("norm1")(h)
            q, k, v = (heads(_dense(self, d, name)(u))
                       for name in ("q_proj", "k_proj", "v_proj"))
            a = causal_attention(_rope(q, positions, self.rope_theta),
                                 _rope(k, positions, self.rope_theta), v,
                                 self.attn_impl)
            h = h + norm("norm2")(
                _dense(self, d, "out_proj")(a.reshape(b, t, d)))
        with jax.named_scope(scopes.LOOP_MLP):
            return h + norm("norm4")(
                GatedMLP(self.d_ff, self.dtype, self.init_std, name="mlp")(
                    norm("norm3")(h)))


class LoopedLM(nn.Module):
    """Decoder-only looped LM.  `__call__`: (B, T) int32 tokens -> the
    last exit's (B, T, vocab) float32 logits.  `token_losses`: the
    exit-weighted training loss of every token."""
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    d_ff: int = 2048
    n_loops: int = 4                # passes through the stack
    exit_beta: float = 0.1          # the entropy term's coefficient
    rope_theta: float = 10000.0
    eps: float = 1e-6
    init_std: float = 0.02
    remat: bool = False             # jax.checkpoint each block but
                                    # its flash kernel's results
    attn_impl: str = "xla"
    dtype: Any = jnp.float32

    # name -> how `train/lm.py` merges the counter over micro-batches and
    # data ranks before it reports it in the step's metrics
    step_counters = {"loop_expected_exit": "mean",
                     "loop_last_exit_mass": "mean"}

    def setup(self):
        self.embed = nn.Embed(self.vocab_size, self.d_model,
                              dtype=self.dtype,
                              embedding_init=_init(self.init_std))
        block_cls = (nn.remat(LoopBlock, policy=KEEP_FLASH_RESIDUALS)
                     if self.remat else LoopBlock)
        for i in range(self.n_layers):
            setattr(self, f"block{i}", block_cls(
                self.n_heads, self.d_ff, self.rope_theta, self.eps,
                self.attn_impl, self.dtype, self.init_std))
        self.final_norm = RMSNorm(self.eps, jnp.float32)
        self.exit_gate = nn.Dense(1, dtype=jnp.float32,
                                  kernel_init=_init(self.init_std))
        self.lm_head = nn.Dense(self.vocab_size, use_bias=False,
                                dtype=jnp.float32,
                                kernel_init=_init(self.init_std))

    def _exit(self, state, targets):
        """One exit from the normed state: `(cross-entropy (B, T), hits)`
        against `targets`, or the logits themselves without them."""
        logits = self.lm_head(state)
        if targets is None:
            return logits
        return (optax.softmax_cross_entropy_with_integer_labels(
            logits, targets), jnp.sum(jnp.argmax(logits, -1) == targets))

    def _passes(self, tokens, targets):
        """Every pass in turn: `(h^R, what each pass's exit gave, stacked
        over the passes)`: its gate (B, T) and, with `targets`, its
        cross-entropy and hits."""
        positions = jnp.arange(tokens.shape[1])

        def one_pass(self, state, _):
            # the carry is the float32 normed state (the embedding before
            # the first pass: exact in either type)
            h = state.astype(self.dtype)
            for i in range(self.n_layers):
                h = getattr(self, f"block{i}")(h, positions)
            with jax.named_scope(scopes.LOOP_EXIT):
                state = self.final_norm(h)                  # float32
                gate = self.exit_gate(state)[..., 0]
                terms = () if targets is None else nn.remat(
                    LoopedLM._exit)(self, state, targets)
            return state, (gate, *terms)

        return nn.scan(one_pass, variable_broadcast="params",
                       split_rngs={"params": False}, length=self.n_loops)(
                           self, self.embed(tokens).astype(jnp.float32),
                           None)

    def __call__(self, tokens, train: bool = True):
        del train                   # no dropout: the family trains without
        state, _ = self._passes(tokens, None)
        with jax.named_scope(scopes.LOOP_EXIT):
            return self._exit(state, None)

    def token_losses(self, tokens, targets, train: bool = True):
        """`(terms, hits)`: the (B, T) float32 loss of every token,
        `sum_r p^r CE^r - beta H(p)`, and how many of the tokens the LAST
        exit predicts right.  Sows the two counters."""
        del train
        _, (gates, ce, hits) = self._passes(tokens, targets)
        with jax.named_scope(scopes.LOOP_EXIT):
            log_p, p = exit_distribution(gates)
            terms = jnp.sum(p * ce, 0) + self.exit_beta * jnp.sum(
                p * log_p, 0)
            exits = jnp.arange(1, self.n_loops + 1, dtype=jnp.float32)
            self.sow(COUNTERS, "loop_expected_exit",
                     jnp.mean(jnp.tensordot(exits, p, 1)))
            self.sow(COUNTERS, "loop_last_exit_mass", jnp.mean(p[-1]))
        return terms, hits[-1]


def looped_lm(vocab_size: int = 32000, d_model: int = 512,
              n_layers: int = 4, n_heads: int = 8,
              d_ff: Optional[int] = None, dtype=jnp.float32,
              n_kv_heads: Optional[int] = None, **kw) -> LoopedLM:
    """`n_kv_heads` is accepted for the LM factories' common signature:
    the family has a key head for every query head."""
    if n_kv_heads not in (None, n_heads):
        raise ValueError(f"the looped LM has a key head for every query "
                         f"head: n_kv_heads {n_kv_heads} != {n_heads}")
    return LoopedLM(vocab_size=vocab_size, d_model=d_model,
                    n_layers=n_layers, n_heads=n_heads,
                    d_ff=d_ff or 4 * d_model, dtype=dtype, **kw)
