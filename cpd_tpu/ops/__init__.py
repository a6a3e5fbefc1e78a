"""Native kernel layer (Pallas/Mosaic) — the TPU analog of the reference's
CUDA extension (CPDtorch/quant/quant_cuda/).  See also quant/ for the XLA
implementations these are bit-identical to."""

from .backend import interpret_mode, require_tpu
from .quantize import quantize_pallas, quantize_pallas_sr
from .qgemm import qgemm_pallas
from .flash_gqa import flash_gqa
from .serve_attn import fused_gather_attention

__all__ = ["interpret_mode", "require_tpu", "quantize_pallas", "quantize_pallas_sr",
           "qgemm_pallas", "flash_gqa", "fused_gather_attention"]
