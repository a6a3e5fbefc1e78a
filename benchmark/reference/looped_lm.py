"""Plain float32 reference of the looped LM (Ouro-2.6B's `config.json`;
Zhu et al., arXiv:2510.25741, stage I): forward pass and the exit-weighted
loss in straightforward `jax.numpy`, no kernels, no scan over the passes,
no mixed precision.

With `N` an RMSNorm of its own scale, `h^0 = E[tokens]`, `L` layers and
`R = total_ut_steps` passes through the SAME `L` layers:

    MHA_l(u):   q, k, v = u W_q, u W_k, u W_v   (heads of hidden / heads;
                rotary positions on q and k, pairs (i, i + half))
                softmax_causal(q k^T / sqrt(head width)) v W_o
    block_l(h): a = h + N2_l(MHA_l(N1_l(h)))
                a + N4_l((silu(m W_gate) * (m W_up)) W_down),  m = N3_l(a)
    pass r:     h^r = N_f(block_L(... block_1(h^{r-1}) ...))
    exit r:     z^r = h^r W_head;  lam^r = sigmoid(h^r . w_g + b_g)
                p^r = lam^r prod_{j<r} (1 - lam^j) for r < R,
                p^R = prod_{j<R} (1 - lam^j)
    loss/token: sum_r p^r CE(z^r, target) - beta H(p),
                H(p) = - sum_r p^r log p^r,  beta = `exit_entropy_beta`

It reads the parameter tree `cpd_tpu.models.looped.LoopedLM`
initialises and shares no code with it.  Departures from the source are
the configuration file's `assumed`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.special import xlogy


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    # x: (T, heads, width); pairs are (i, i + half)
    t, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _head_attention(q, k, v):
    """One head, causal: (T, width) each."""
    t, width = q.shape
    s = q @ k.T / jnp.sqrt(float(width))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jax.nn.softmax(s, -1) @ v


def _attention(u, p, config):
    t = u.shape[0]
    heads, theta = config["num_attention_heads"], float(config["rope_theta"])
    split = lambda name: (u @ p[name]["kernel"]).reshape(t, heads, -1)
    q, k, v = _rope(split("q_proj"), theta), _rope(split("k_proj"), theta), (
        split("v_proj"))
    # a head at a time, (T, T) scores, recomputed in a backward pass
    a = jax.lax.map(jax.checkpoint(lambda x: _head_attention(*x)),
                    tuple(x.transpose(1, 0, 2) for x in (q, k, v)))
    return a.transpose(1, 0, 2).reshape(t, -1) @ p["out_proj"]["kernel"]


def _block(h, p, config):
    eps = config["rms_norm_eps"]
    norm = lambda x, name: _rms_norm(x, p[name]["scale"], eps)
    a = h + norm(_attention(norm(h, "norm1"), p, config), "norm2")
    m, mlp = norm(a, "norm3"), p["mlp"]
    return a + norm(
        (jax.nn.silu(m @ mlp["gate_proj"]["kernel"])
         * (m @ mlp["up_proj"]["kernel"])) @ mlp["down_proj"]["kernel"],
        "norm4")


EXIT_TOKENS = 1024      # tokens whose logits are live at once


def _exits(states, params, targets):
    """(cross-entropy (R, T), gate value (R, T)) of the R exits from their
    normed states (R, T, hidden): z = h W_head, its cross-entropy against
    the token's target, and the gate's w_g . h + b_g.  A token's logits
    depend on no other token's, so the rows are taken `EXIT_TOKENS` at a
    time where they divide T, every exit's blocks in one `lax.map`: in a
    backward pass the head's gradient then adds up in one place."""
    def some(ht):
        h, target = ht
        logp = jax.nn.log_softmax(h @ params["lm_head"]["kernel"], -1)
        gate = h @ params["exit_gate"]["kernel"][:, 0] + (
            params["exit_gate"]["bias"][0])
        return -jnp.take_along_axis(logp, target[:, None], 1)[:, 0], gate

    r, t, _ = states.shape
    rows = EXIT_TOKENS if t % EXIT_TOKENS == 0 else t
    ce, gate = jax.lax.map(jax.checkpoint(some), (
        states.reshape(r * t // rows, rows, -1),
        jnp.tile(targets, r).reshape(r * t // rows, rows)))
    return ce.reshape(r, t), gate.reshape(r, t)


def exit_probabilities(gates):
    """p (R, T) from the R exits' gate values (R, T)."""
    lam = jax.nn.sigmoid(gates[:-1])
    before = jnp.concatenate([jnp.ones_like(gates[:1]),
                              jnp.cumprod(1.0 - lam, 0)])   # prod_{j < r}
    return jnp.concatenate([lam * before[:-1], before[-1:]])


def _sequence_loss(params, tokens, targets, config):
    block = jax.checkpoint(lambda h, p: _block(h, p, config))
    h = params["embed"]["embedding"][tokens]
    states = []
    for _ in range(config["total_ut_steps"]):
        for i in range(config["num_hidden_layers"]):
            h = block(h, params[f"block{i}"])       # the same in every pass
        h = _rms_norm(h, params["final_norm"]["scale"],
                      config["rms_norm_eps"])
        states.append(h)
    ce, gates = _exits(jnp.stack(states), params, targets)
    p = exit_probabilities(gates)
    entropy = -xlogy(p, p).sum(0)
    return ((p * ce).sum(0) - config["exit_entropy_beta"] * entropy).mean()


def loss(params, tokens, targets, config):
    """Mean exit-weighted loss over a (B, T) batch, one sequence after the
    other, at the matmul precision a float32 reference needs on a TPU.

    The `jax.checkpoint`s (a sequence, a block, a block of an exit's
    tokens, a head), the `lax.map`s and the `lax.scan` over the sequences
    change no value: a backward pass through
    this function recomputes instead of keeping, and takes the sequences
    in turn, so that `jax.grad` of it fits the chip beside four copies of
    the parameters."""
    one = jax.checkpoint(lambda p, a, b: _sequence_loss(p, a, b, config))
    with jax.default_matmul_precision("highest"):
        total, _ = jax.lax.scan(
            lambda acc, ab: (acc + one(params, *ab), None),
            jnp.zeros((), jnp.float32), (tokens, targets))
        return total / tokens.shape[0]
