"""Plain float32 reference of the latent-attention mixture-of-experts LM
(the DeepSeek-V3 block; Moonlight-16B-A3B's `config.json`) as ONE chip of
an expert-parallel group holds it: forward pass and next-token loss in
straightforward `jax.numpy`, no kernels, no sort, no grouped product, no
mixed precision.

Every layer: x <- x + Attn(RMSNorm(x)); x <- x + F(RMSNorm(x)).

Attn (latent attention, no query compression): q = h W_q, a head split
into a part without positions and a rotary part; c = h W_kva, split into
the latent c_kv and ONE rotary key k_rope for all heads; [k_nope | v] =
RMSNorm(c_kv) W_kvb.  Scores are q_nope . k_nope + q_rope . k_rope over
sqrt(the q/k head width), causal softmax, values, W_o.

F in the first `first_k_dense_replace` layers: a SiLU-gated MLP.  After
them: s = sigmoid(h W_r) over all `n_routed_experts_published` experts; the
`num_experts_per_tok` largest of s + bias are selected (by counting how many
scores beat each one: no sort), gates are s over the selected, renormalised
and scaled by `routed_scaling_factor`; F = sum over the selected experts
THAT THIS CHIP HOLDS (`n_routed_experts` of them from `expert_first`) of
gate x expert(h), each held expert a dense pass over all tokens times its
gate or zero, plus the shared expert.  What the absent experts would add is
left out, as their owners' chips add it.  The head is untied and covers the
rows of the vocabulary this chip holds.

It reads the parameter tree `cpd_tpu.models.mla_moe.MLAMoELM` initialises
and shares no code with it.  Departures from the source are the
configuration file's `assumed`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


def _gated_mlp(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _head_attention(q_nope, q_rope, k_nope, k_rope, v):
    """One head, causal: the rotary key is the same for every head."""
    t = q_nope.shape[0]
    width = q_nope.shape[1] + q_rope.shape[1]
    s = (q_nope @ k_nope.T + q_rope @ k_rope.T) / jnp.sqrt(float(width))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jax.nn.softmax(s, -1) @ v


def _attention(h, p, config):
    t = h.shape[0]
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, theta = config["kv_lora_rank"], float(config["rope_theta"])
    q = (h @ p["q_proj"]["kernel"]).reshape(t, heads, nope + rope)
    c = h @ p["kv_down"]["kernel"]
    kv = (_rms_norm(c[:, :rank], p["kv_norm"]["scale"],
                    config["rms_norm_eps"])
          @ p["kv_up"]["kernel"]).reshape(t, heads, -1)
    q_rope = _rope(q[..., nope:], theta)
    k_rope = _rope(c[:, None, rank:], theta)[:, 0]          # (T, rope)
    # a head at a time, (T, T) scores, recomputed in a backward pass
    a = jax.lax.map(
        jax.checkpoint(lambda x: _head_attention(x[0], x[1], x[2], k_rope,
                                                 x[3])),
        (q[..., :nope].transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
         kv[..., :nope].transpose(1, 0, 2),
         kv[..., nope:].transpose(1, 0, 2)))
    return a.transpose(1, 0, 2).reshape(t, -1) @ p["out_proj"]["kernel"]


def _routed(h, p, config):
    """This chip's share of the routed experts' sum, (T, d) -> (T, d)."""
    k = config["num_experts_per_tok"]
    first, held = config.get("expert_first", 0), config["n_routed_experts"]
    s = jax.nn.sigmoid(h @ p["router"])                     # (T, E)
    sel = s + jax.lax.stop_gradient(p["score_bias"])
    # expert e is selected when fewer than k others beat it (the lower id
    # wins a tie)
    ids = jnp.arange(s.shape[1])
    beats = (sel[:, :, None] > sel[:, None, :]) | (
        (sel[:, :, None] == sel[:, None, :])
        & (ids[:, None] < ids[None, :]))        # [t, j, e]: j beats e
    chosen = beats.sum(1) < k                               # (T, E)
    gates = config["routed_scaling_factor"] * s * chosen / (
        (s * chosen).sum(-1, keepdims=True) + 1e-20)

    def add_expert(out, w):
        w_gate, w_up, w_down, e = w
        gate = jnp.take(gates, e, axis=1)[:, None]          # 0 if not chosen
        return out + gate * _gated_mlp(h, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(
        jax.checkpoint(add_expert), jnp.zeros_like(h),
        (p["experts_gate"], p["experts_up"], p["experts_down"],
         first + jnp.arange(held)))
    return out


def _block(x, p, routed, config):
    eps = config["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["norm1"]["scale"], eps), p["attn"],
                       config)
    h = _rms_norm(x, p["norm2"]["scale"], eps)
    if not routed:
        m = p["mlp"]
        return x + _gated_mlp(h, m["gate_proj"]["kernel"],
                              m["up_proj"]["kernel"],
                              m["down_proj"]["kernel"])
    sh = p["shared"]
    return x + _routed(h, p["moe"], config) + _gated_mlp(
        h, sh["gate_proj"]["kernel"], sh["up_proj"]["kernel"],
        sh["down_proj"]["kernel"])


def logits(params, tokens, config):
    """(T,) int32 tokens of ONE sequence -> (T, held vocabulary) logits."""
    x = params["embed"]["embedding"][tokens]
    for i in range(config["num_hidden_layers"]):
        # static: which F the layer has, and every width
        block = jax.checkpoint(
            lambda x, p, routed=i >= config["first_k_dense_replace"]:
            _block(x, p, routed, config))
        x = block(x, params[f"block{i}"])
    return _rms_norm(x, params["norm_f"]["scale"],
                     config["rms_norm_eps"]) @ params["lm_head"]["kernel"]


def _sequence_loss(params, tokens, targets, config):
    logp = jax.nn.log_softmax(logits(params, tokens, config), -1)
    return -jnp.take_along_axis(logp, targets[:, None], 1).mean()


def loss(params, tokens, targets, config):
    """Mean next-token cross-entropy over a (B, T) batch, one sequence
    after the other, at the matmul precision a float32 reference needs on
    a TPU.

    The `jax.checkpoint`s (a sequence, a layer, a head, an expert) and the
    `lax.scan` over the sequences change no value: a backward pass through
    this function recomputes instead of keeping, and takes the sequences
    in turn, adding each one's gradient into the last one's, so that
    `jax.grad` of it fits the chip beside four copies of the parameters
    (compiled for a described v5e at 2 x 8,192 tokens: 2.5 GiB of
    temporaries; as a Python loop over the sequences, whose backward
    passes the compiler interleaves, 7.5)."""
    one = jax.checkpoint(lambda p, a, b: _sequence_loss(p, a, b, config))
    with jax.default_matmul_precision("highest"):
        total, _ = jax.lax.scan(
            lambda acc, ab: (acc + one(params, *ab), None),
            jnp.zeros((), jnp.float32), (tokens, targets))
        return total / tokens.shape[0]
