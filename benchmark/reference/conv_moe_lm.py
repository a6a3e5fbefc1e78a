"""Plain float32 reference of the hybrid short-convolution / attention
mixture-of-experts LM (the LFM2 MoE block; LFM2-24B-A2B's `config.json`)
as ONE chip of an expert-parallel group holds it: forward pass and
next-token loss in straightforward `jax.numpy`, no kernels, no buffer, no
sort, no mixed precision.

Every layer: x <- x + M(RMSNorm(x)); x <- x + F(RMSNorm(x)), M the mixer
`layer_types` names for the layer:

conv: [B | C | x~] = h W_in, split in that order; z = B * x~;
v_t = sum over the `conv_L_cache` taps j of w_j * z_{t - (L - 1 - j)},
z = 0 before the sequence's first token, written out as that sum of
shifted copies; M = (C * v) W_out.

full_attention: q = h W_q, k = h W_k, v = h W_v in heads of
hidden / `num_attention_heads`, `num_key_value_heads` of them for k and
v; every q and k head RMS-normed over its width with a scale of its own
(q_norm, k_norm), then rotary positions (pairs (i, i + half), base
`rope_parameters.rope_theta`); a query head reads the key head of its
group; causal softmax over sqrt(head width), values, W_o.

F in the first `num_dense_layers` layers: a SiLU-gated MLP.  After them:
this chip's share of the routed experts, `reference/mla_moe_lm.py`'s
`_routed` (sigmoid scores over all `num_experts_published` experts, the
`num_experts_per_tok` largest of score + bias selected by counting, gates
renormalised and scaled, a scan over the `num_experts` experts held from
`expert_first`, each a dense pass over every token), with no shared
expert.  The head is the embedding's transpose over the held rows.

It reads the parameter tree `cpd_tpu.models.conv_moe.ConvMoELM`
initialises and shares no code with it.  Departures from the source are
the configuration file's `assumed`; of this module's own: the attention is
a head at a time under `jax.checkpoint` with k and v repeated over their
groups, the routed experts each a dense pass over every token, and the
renormalisation of the gates adds 1e-20 where the source adds 1e-6 (the
program's, and under bf16's rounding of a gate).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.mla_moe_lm import (_gated_mlp, _rms_norm, _rope,
                                            _routed)


def _short_conv(h, p, config):
    t, d = h.shape
    bcx = h @ p["in_proj"]["kernel"]
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = b * x
    taps = config["conv_L_cache"]
    shifted = lambda s: jnp.concatenate([jnp.zeros((s, d), z.dtype),
                                         z[:t - s]]) if s else z
    v = sum(p["taps"][j] * shifted(taps - 1 - j) for j in range(taps))
    return (c * v) @ p["out_proj"]["kernel"]


def _head_attention(q, k, v):
    """One head, causal: (T, width) each."""
    t, width = q.shape
    s = q @ k.T / jnp.sqrt(float(width))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jax.nn.softmax(s, -1) @ v


def _attention(h, p, config):
    t = h.shape[0]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    eps = config["norm_eps"]
    theta = float(config["rope_parameters"]["rope_theta"])
    split = lambda name, n: (h @ p[name]["kernel"]).reshape(t, n, -1)
    q = _rope(_rms_norm(split("q_proj", heads), p["q_norm"]["scale"], eps),
              theta)
    k = _rope(_rms_norm(split("k_proj", kv_heads), p["k_norm"]["scale"],
                        eps), theta)
    group = lambda x: jnp.repeat(x, heads // kv_heads, 1)
    # a head at a time, (T, T) scores, recomputed in a backward pass
    a = jax.lax.map(jax.checkpoint(lambda x: _head_attention(*x)),
                    tuple(x.transpose(1, 0, 2) for x in
                          (q, group(k), group(split("v_proj", kv_heads)))))
    return a.transpose(1, 0, 2).reshape(t, -1) @ p["out_proj"]["kernel"]


def _routed_config(config):
    """`_routed`'s keys, from this family's."""
    return {"num_experts_per_tok": config["num_experts_per_tok"],
            "expert_first": config.get("expert_first", 0),
            "n_routed_experts": config["num_experts"],
            "routed_scaling_factor": config["routed_scaling_factor"]}


def _block(x, p, mixer, routed, config):
    eps = config["norm_eps"]
    h = _rms_norm(x, p["norm1"]["scale"], eps)
    if mixer == "conv":
        x = x + _short_conv(h, p["conv"], config)
    elif mixer == "full_attention":
        x = x + _attention(h, p["attn"], config)
    else:
        raise ValueError(f"unknown layer type {mixer!r}")
    h = _rms_norm(x, p["norm2"]["scale"], eps)
    if not routed:
        m = p["mlp"]
        return x + _gated_mlp(h, m["gate_proj"]["kernel"],
                              m["up_proj"]["kernel"],
                              m["down_proj"]["kernel"])
    return x + _routed(h, p["moe"], _routed_config(config))


def logits(params, tokens, config):
    """(T,) int32 tokens of ONE sequence -> (T, held vocabulary) logits."""
    emb = params["embed"]["embedding"]
    x = emb[tokens]
    for i, mixer in enumerate(config["layer_types"]):
        # static: which mixer and which F the layer has, and every width
        block = jax.checkpoint(
            lambda x, p, mixer=mixer,
            routed=i >= config["num_dense_layers"]:
            _block(x, p, mixer, routed, config))
        x = block(x, params[f"block{i}"])
    return _rms_norm(x, params["norm_f"]["scale"], config["norm_eps"]) @ emb.T


def _sequence_loss(params, tokens, targets, config):
    logp = jax.nn.log_softmax(logits(params, tokens, config), -1)
    return -jnp.take_along_axis(logp, targets[:, None], 1).mean()


def loss(params, tokens, targets, config):
    """Mean next-token cross-entropy over a (B, T) batch, one sequence
    after the other, at the matmul precision a float32 reference needs on
    a TPU.  The `jax.checkpoint`s (a sequence, a layer, a head, an expert)
    and the `lax.scan` over the sequences change no value: a backward pass
    recomputes instead of keeping and takes the sequences in turn, so that
    `jax.grad` of it fits the chip beside four copies of the parameters."""
    one = jax.checkpoint(lambda p, a, b: _sequence_loss(p, a, b, config))
    with jax.default_matmul_precision("highest"):
        total, _ = jax.lax.scan(
            lambda acc, ab: (acc + one(params, *ab), None),
            jnp.zeros((), jnp.float32), (tokens, targets))
        return total / tokens.shape[0]
