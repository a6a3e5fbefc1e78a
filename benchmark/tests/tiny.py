"""Tiny stand-ins for the benchmark's data files, for tests on the CPU:
the same keys as `configs/*.json` and `traffic/*.json`, toy sizes."""

from __future__ import annotations

import json
import os

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "configs", "resnet50_imagenet.json")) as _f:
    _RESNET50 = json.load(_f)

READINGS = ("update_rel_err", "update_rel_err_worst_part",
            "update_rel_err_head", "grad_norm_gap", "change_norm_gap",
            "loss_gap")


def limits(**held) -> dict:
    """A traffic file's limits: every reading of `check.py` named, those
    not in `held` stated as not compared."""
    out = {}
    for name in READINGS:
        out[name + "_max"] = held.get(name)
        out[name + "_why"] = "a test's"
    return out


REDUCE = {"use_aps": True, "grad_exp": 5, "grad_man": 2, "mode": "faithful",
          "donate": True}

LM_CONFIG = {
    "runner": "train_lm", "item": "token", "model": "transformer_lm",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "vocab_size": 256, "num_hidden_layers": 2,
    "model_kwargs": {"attn_impl": "flash", "dtype": "bfloat16"},
    "classes": 256,
    "optimizer": {"name": "sgd", "momentum": 0.9, "weight_decay": 0.0,
                  "lr": 0.01},
    "ops_per_item": "dense_lm:train_flops_per_token",
    "reference": "dense_lm:loss", "head_part": "embed",
    "init_loss_band": [0.8, 1.5], "reference_loss_rtol": 0.05,
}
LM_TRAFFIC = {"batch_per_chip": 2, "seq_len": 128, "group": 1, "ring": 3,
              "reduce": REDUCE, **limits()}

VISION_CONFIG = {
    "runner": "train_vision", "item": "img", "model": "resnet50",
    "model_kwargs": {"num_classes": 10, "dtype": "bfloat16"},
    "image_size": 32, "classes": 10,
    "optimizer": {"name": "sgd", "momentum": 0.9, "weight_decay": 1e-4,
                  "lr_per_256_items": 0.1},
    "ops_per_item": "resnet:train_flops_per_image",
    "reference": "resnet:loss", "head_part": "fc",
    # the seeded weights the committed configuration is checked on
    "residual_last_bn_scale": _RESNET50["residual_last_bn_scale"],
    # bf16 through 50 layers whose batch statistics are over 4 images of
    # 1x1 to 8x8 pixels: a tenth of the loss at this size (float32 compute
    # agrees with the reference to 6e-4, see test_harness)
    "init_loss_band": [0.8, 1.5], "reference_loss_rtol": 0.15,
}
VISION_TRAFFIC = {"batch_per_chip": 4, "group": 2, "ring": 3,
                  "reduce": REDUCE, **limits()}


def found(config: dict, traffic: dict, chips: int) -> dict:
    """What `run.discover()` returns for one cell."""
    return {"cell": {"name": "tiny", "chips": chips}, "config": config,
            "traffic": traffic, "metrics": {"end_to_end": {}, "per_layer": {}}}
