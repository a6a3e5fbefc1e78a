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


def _head_attention(q, k, v):
    """One query head against its key-value head, causal: (T, hd) each."""
    t, hd = q.shape
    s = (q @ k.T) / jnp.sqrt(float(hd))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jax.nn.softmax(s, -1) @ v


def _block(x, p, heads, kv):
    """One pre-LayerNorm block on one sequence, (T, d) -> (T, d)."""
    t, d = x.shape
    hd = d // heads
    h = _layer_norm(x, p["ln1"])
    q = (h @ p["wq"]["kernel"]).reshape(t, heads, hd)
    kvp = (h @ p["wkv"]["kernel"]).reshape(t, kv, 2, hd)
    q, k, v = _rope(q), _rope(kvp[:, :, 0]), kvp[:, :, 1]
    # query head i reads key-value head i // (heads // kv).  A head at a
    # time, (T, T) scores, recomputed in a backward pass
    of = jnp.arange(heads) // (heads // kv)
    a = jax.lax.map(
        jax.checkpoint(lambda qkv: _head_attention(*qkv)),
        (q.transpose(1, 0, 2), k.transpose(1, 0, 2)[of],
         v.transpose(1, 0, 2)[of]))
    x = x + a.transpose(1, 0, 2).reshape(t, d) @ p["wo"]["kernel"]
    h = _layer_norm(x, p["ln2"])
    return x + _gelu_tanh(h @ p["wi"]["kernel"]) @ p["wo_mlp"]["kernel"]


def logits(params, tokens, config):
    """(T,) int32 tokens of ONE sequence -> (T, vocab) float32 logits."""
    heads = config["num_attention_heads"]
    kv = config["num_key_value_heads"]
    emb = params["embed"]["embedding"].astype(jnp.float32)
    x = emb[tokens]
    block = jax.checkpoint(_block, static_argnums=(2, 3))
    for i in range(config["num_hidden_layers"]):
        x = block(x, params[f"block{i}"], heads, kv)
    return _layer_norm(x, params["ln_f"]) @ emb.T


def _sequence_loss(params, tokens, targets, config):
    logp = jax.nn.log_softmax(logits(params, tokens, config), -1)
    return -jnp.take_along_axis(logp, targets[:, None], 1).mean()


def loss(params, tokens, targets, config):
    """Mean next-token cross-entropy over a (B, T) batch, one sequence at
    a time, at the matmul precision a float32 reference needs on a TPU.

    The `jax.checkpoint`s (a sequence, a layer, an attention head) change
    no value: they make a backward pass through this function recompute
    instead of keep, so that `jax.grad` of it fits beside nothing else on
    a 16 GB chip at 2 x 4,096 tokens and the published widths."""
    one = jax.checkpoint(
        lambda p, a, b: _sequence_loss(p, a, b, config))
    with jax.default_matmul_precision("highest"):
        rows = [one(params, tokens[i], targets[i])
                for i in range(tokens.shape[0])]
        return sum(rows) / len(rows)
