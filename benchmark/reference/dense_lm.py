"""Plain float32 reference of the dense decoder-only LM the `train_lm`
runner trains: forward pass and next-token loss in straightforward
`jax.numpy`, no kernels, no sharding, no mixed precision.

It follows the published StarCoder2 block (pre-LayerNorm, rotary positions
on half-split head dimensions, grouped-query causal attention, two-matrix
tanh-GELU MLP, tied output head) with the departures the configuration's
file lists under `assumed` (no bias terms, rope_theta 10000).  It reads the
parameter tree `cpd_tpu.models.transformer.TransformerLM` initialises, and
shares no code with it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, theta=10000.0):
    # x: (T, heads, head_dim); pairs are (i, i + half)
    t, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def logits(params, tokens, config):
    """(T,) int32 tokens of ONE sequence -> (T, vocab) float32 logits."""
    heads = config["num_attention_heads"]
    kv = config["num_key_value_heads"]
    d = config["hidden_size"]
    hd = d // heads
    t = tokens.shape[0]
    emb = params["embed"]["embedding"].astype(jnp.float32)
    x = emb[tokens]
    mask = jnp.tril(jnp.ones((t, t), bool))
    for i in range(config["num_hidden_layers"]):
        p = params[f"block{i}"]
        h = _layer_norm(x, p["ln1"])
        q = (h @ p["wq"]["kernel"]).reshape(t, heads, hd)
        kvp = (h @ p["wkv"]["kernel"]).reshape(t, kv, 2, hd)
        q, k, v = _rope(q), _rope(kvp[:, :, 0]), kvp[:, :, 1]
        rep = heads // kv        # query heads [g*rep, (g+1)*rep) read kv head g
        groups = []
        for g in range(kv):      # a group at a time: (rep, T, T) scores
            s = jnp.einsum("qhd,kd->hqk", q[:, g * rep:(g + 1) * rep],
                           k[:, g]) / jnp.sqrt(float(hd))
            s = jnp.where(mask[None], s, -jnp.inf)
            groups.append(jnp.einsum("hqk,kd->qhd", jax.nn.softmax(s, -1),
                                     v[:, g]))
        a = jnp.concatenate(groups, axis=1)
        x = x + a.reshape(t, d) @ p["wo"]["kernel"]
        h = _layer_norm(x, p["ln2"])
        x = x + _gelu_tanh(h @ p["wi"]["kernel"]) @ p["wo_mlp"]["kernel"]
    return _layer_norm(x, params["ln_f"]) @ emb.T


def loss(params, tokens, targets, config):
    """Mean next-token cross-entropy over a (B, T) batch, one sequence at
    a time, at the matmul precision a float32 reference needs on a TPU."""
    with jax.default_matmul_precision("highest"):
        rows = []
        for i in range(tokens.shape[0]):
            lg = logits(params, tokens[i], config)
            logp = jax.nn.log_softmax(lg, -1)
            rows.append(-jnp.take_along_axis(
                logp, targets[i][:, None], 1).mean())
        return sum(rows) / len(rows)
