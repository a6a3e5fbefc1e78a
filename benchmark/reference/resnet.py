"""Plain float32 reference of ResNet-50 (He et al. 2015, the v1.5 layout
torchvision builds: stride on the 3x3) in training mode: forward pass with
batch statistics and the mean cross-entropy loss, in straightforward
`jax.numpy`/`lax.conv`, no mixed precision.  It reads the parameter tree
`cpd_tpu.models.resnet.ResNet` initialises and shares no code with it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

STAGES = (3, 4, 6, 3)


def _conv(x, p, stride=1, pad=0):
    return lax.conv_general_dilated(
        x, p["kernel"], (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p, eps=1e-5):
    mean = x.mean((0, 1, 2))
    var = ((x - mean) ** 2).mean((0, 1, 2))
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _bottleneck(x, p, stride):
    y = jax.nn.relu(_bn(_conv(x, p["conv1"]), p["bn1"]))
    y = jax.nn.relu(_bn(_conv(y, p["conv2"], stride, 1), p["bn2"]))
    y = _bn(_conv(y, p["conv3"]), p["bn3"])
    if "downsample_conv" in p:
        x = _bn(_conv(x, p["downsample_conv"], stride), p["downsample_bn"])
    return jax.nn.relu(y + x)


def _stem(images, conv, bn):
    x = jax.nn.relu(_bn(_conv(images.astype(jnp.float32), conv, 2, 3), bn))
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                             [(0, 0), (1, 1), (1, 1), (0, 0)])


def logits(params, images):
    x = jax.checkpoint(_stem)(images, params["stem_conv"], params["stem_bn"])
    # `jax.checkpoint` makes a backward pass recompute a block from its
    # input (256 images' activations do not fit otherwise); no value changes
    block = jax.checkpoint(_bottleneck, static_argnums=(2,))
    for stage, blocks in enumerate(STAGES):
        for b in range(blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            x = block(x, params[f"layer{stage + 1}_block{b}"], stride)
    x = x.mean((1, 2))
    return x @ params["fc"]["kernel"] + params["fc"]["bias"]


def loss(params, images, labels, config=None):
    """Mean cross-entropy of one replica's batch (batch statistics are
    taken over exactly these images)."""
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(logits(params, images), -1)
        return -jnp.take_along_axis(logp, labels[:, None], 1).mean()

