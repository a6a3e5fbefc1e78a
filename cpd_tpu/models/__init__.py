"""Model zoo — parity with the reference's example models plus a registry.

The reference selects models by dict key (`models['res_cifar']`,
reference: example/ResNet18/tools/mix.py:82); `get_model(name)` is the same
idea for all families.
"""

from .resnet_cifar import ResNetCIFAR, resnet18_cifar
from .davidnet import DavidNet, davidnet
from .resnet import ResNet, resnet18, resnet34, resnet50, resnet101
from .fcn import FCN, FCNHead, fcn_r50_d8
from .tiny import TinyCNN, tiny_cnn
from .transformer import TransformerLM, lm_param_specs, transformer_lm
from .pipeline_lm import PipelinedLM, pipelined_lm, pp_param_specs
from .moe import MoETransformerLM, moe_lm, moe_param_specs
from .mla_moe import MLAMoELM, mla_moe_lm
from .looped import LoopedLM, looped_lm
from .conv_moe import ConvMoELM, conv_moe_lm
from .davidnet_graph import graph_davidnet
from .generate import generate
from .vit import ViT, vit

_REGISTRY = {
    "res_cifar": resnet18_cifar,      # reference name (mix.py:82)
    "resnet18_cifar": resnet18_cifar,
    "davidnet": davidnet,
    "resnet18": resnet18,
    "resnet34": resnet34,
    "resnet50": resnet50,
    "resnet101": resnet101,
    "fcn_r50_d8": fcn_r50_d8,
    "tiny": tiny_cnn,                 # smoke-test model (models/tiny.py)
    "transformer_lm": transformer_lm,
    "pipelined_lm": pipelined_lm,
    "moe_lm": moe_lm,
    "mla_moe_lm": mla_moe_lm,         # latent attention + routed experts
    "looped_lm": looped_lm,           # one stack run n_loops times, exit gate
    "conv_moe_lm": conv_moe_lm,       # short convolutions + QK-normed GQA
    "davidnet_graph": graph_davidnet,  # dict-graph definition (TorchGraph)
    "vit": vit,                       # RoPE-ViT encoder (models/vit.py)
}


def get_model(name: str, **kwargs):
    """Instantiate a model by registry name."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)


__all__ = ["ResNetCIFAR", "resnet18_cifar", "DavidNet", "davidnet",
           "ResNet", "resnet18", "resnet34", "resnet50", "resnet101",
           "FCN", "FCNHead", "fcn_r50_d8", "TinyCNN", "tiny_cnn",
           "TransformerLM", "transformer_lm", "lm_param_specs",
           "PipelinedLM", "pipelined_lm", "pp_param_specs",
           "MoETransformerLM", "moe_lm", "moe_param_specs",
           "MLAMoELM", "mla_moe_lm", "LoopedLM", "looped_lm",
           "ConvMoELM", "conv_moe_lm",
           "graph_davidnet", "generate", "get_model"]
