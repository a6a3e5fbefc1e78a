"""The names of a step's layers inside the compiled program.

A train step is one compiled program; the host cannot see inside it
(docs/OBSERVABILITY.md, the `step` span).  The device trace can: every
operation carries jax's name stack as metadata (`tf_op` in a TPU trace,
`op_name=` in the compiled HLO's text), so a `jax.named_scope` entered
where a layer's work is traced names that work in every profile, at no
cost to the program: a scope adds no equation and changes no value.

This module is the one home of the scope strings.  Call sites write
`with jax.named_scope(scopes.REDUCE):` (or use it as a decorator); the
benchmark's reader (`benchmark/trace_scopes.py`) matches the same strings.
Stdlib-only, like the rest of `obs`.

| scope | entered in | covers |
| --- | --- | --- |
| `cpd.loss_grad` | `train/grads.py`, `parallel/overlap.py` | forward and backward; jax's own `jvp(` / `transpose(` markers deeper in the stack split the two |
| `cpd.emulate_node` | `parallel/emulate.py`, called by `train/grads.py` | the in-chip emulated-node reduction |
| `cpd.reduce` | `parallel/dist.py:sum_gradients` | the whole gradient reduction, for every caller |
| `aps.max_exp` / `aps.scale` / `aps.unscale` | `parallel/aps.py` | the APS passes (the maximum includes its `pmax`) |
| `wire.cast` | `parallel/dist.py` | the eXmY cast before (and, in `fast` mode, after) the wire |
| `wire.pack` / `wire.unpack` | `quant/numerics.py` | eXmY values to and from wire bytes |
| `wire.collective` | `parallel/dist.py`, `parallel/ring.py` | the collectives themselves, whatever XLA renames or combines them into |
| `reduce.scan` | `parallel/reduction.py` | the ordered requantised sum over ranks |
| `reduce.local` | `parallel/dist.py` | the faithful reduction over an axis of one rank: no codec, no collective, each leaf in its own shape (`reduce.scan` inside it).  XLA fuses all of it into the update's fusions, whose root is `cpd.optimizer`'s: in a device trace it owns nothing, `cpd.optimizer` includes it, and the pipeline's cost at one rank is read by the e5m2-APS twin minus its fp32 control |
| `cpd.optimizer` | `train/step.py`, `train/lm.py` | `tx.update` and `apply_updates` (or the custom `update_fn`) |
| `cpd.metrics` | `train/step.py`, `train/lm.py` | the step's own telemetry `psum`s, and the batch statistics' `pmean` |
| `cpd.mla` | `models/mla_moe.py` | latent attention with its projections (the kernel's scope nests under it) |
| `cpd.moe_router` / `cpd.moe_dispatch` / `cpd.moe_experts` / `cpd.moe_combine` | `models/mla_moe.py:RoutedExperts` | scores, selection and gates / sort, group sizes and the gather of rows / the three grouped products and the gating product / the sum back into tokens |
| `cpd.moe_shared` / `cpd.dense_mlp` | `models/mla_moe.py` | the shared expert / a leading layer's dense gated MLP |
| `cpd.loop_attn` / `cpd.loop_mlp` | `models/looped.py:LoopBlock` | a looped block's attention (norms N1 and N2, projections, rotary; the kernels' scopes nest under it) / its gated MLP with norms N3 and N4; every pass of the loop |
| `cpd.loop_exit` | `models/looped.py:LoopedLM` | a pass's exit: final norm, gate, head and cross-entropy, and the exit weighting of the loss |
| `cpd.conv_mixer` / `cpd.gqa_attn` | `models/conv_moe.py:ConvMoEBlock` | a conv layer's mixer: norm N1, `in_proj`, gating, the causal taps, `out_proj` and the residual add / an attention layer's mixer: N1, projections, the per-head q and k norms, rotary, `out_proj` and the residual add (the kernels' scopes nest under it); the feed-forward parts keep `cpd.dense_mlp` and `cpd.moe_*` |
| `kernel.<name>` | `ops/*.py`, around each `pl.pallas_call` | one Pallas kernel; the call's `name=` is the same `<name>` |

Ownership, as the reader applies it: an operation belongs to the LAST
`cpd.*` or `kernel.*` component of its name stack; `aps.*`, `wire.*` and
`reduce.*` refine it.  jax wraps a scope entered under a transformation
in that transformation's marker (`jvp(cpd.reduce)` when `overlap_reduce`
runs the reduction inside the backward pass); the reader unwraps it.
"""

from __future__ import annotations

__all__ = ["LOSS_GRAD", "EMULATE_NODE", "REDUCE", "OPTIMIZER", "METRICS",
           "MLA", "MOE_ROUTER", "MOE_DISPATCH", "MOE_EXPERTS", "MOE_COMBINE",
           "MOE_SHARED", "DENSE_MLP", "LOOP_ATTN", "LOOP_MLP", "LOOP_EXIT",
           "CONV_MIXER", "GQA_ATTN",
           "APS_MAX_EXP", "APS_SCALE", "APS_UNSCALE", "WIRE_CAST",
           "WIRE_PACK", "WIRE_UNPACK", "WIRE_COLLECTIVE", "REDUCE_SCAN",
           "REDUCE_LOCAL",
           "KERNEL_PREFIX", "KERNELS", "kernel_name"]

LOSS_GRAD = "cpd.loss_grad"
EMULATE_NODE = "cpd.emulate_node"
REDUCE = "cpd.reduce"
OPTIMIZER = "cpd.optimizer"
METRICS = "cpd.metrics"

# model layers (models/mla_moe.py); all nest under LOSS_GRAD
MLA = "cpd.mla"
MOE_ROUTER = "cpd.moe_router"
MOE_DISPATCH = "cpd.moe_dispatch"
MOE_EXPERTS = "cpd.moe_experts"
MOE_COMBINE = "cpd.moe_combine"
MOE_SHARED = "cpd.moe_shared"
DENSE_MLP = "cpd.dense_mlp"

# model layers (models/looped.py); all nest under LOSS_GRAD
LOOP_ATTN = "cpd.loop_attn"
LOOP_MLP = "cpd.loop_mlp"
LOOP_EXIT = "cpd.loop_exit"

# model layers (models/conv_moe.py); all nest under LOSS_GRAD
CONV_MIXER = "cpd.conv_mixer"
GQA_ATTN = "cpd.gqa_attn"

APS_MAX_EXP = "aps.max_exp"
APS_SCALE = "aps.scale"
APS_UNSCALE = "aps.unscale"
WIRE_CAST = "wire.cast"
WIRE_PACK = "wire.pack"
WIRE_UNPACK = "wire.unpack"
WIRE_COLLECTIVE = "wire.collective"
REDUCE_SCAN = "reduce.scan"
REDUCE_LOCAL = "reduce.local"

KERNEL_PREFIX = "kernel."
KERNEL_QUANTIZE = "kernel.quantize"
KERNEL_QUANTIZE_ADD = "kernel.quantize_add"
KERNEL_QUANTIZE_ADD_SR = "kernel.quantize_add_sr"
KERNEL_QUANTIZE_SR = "kernel.quantize_sr"
KERNEL_WIRE_HOP = "kernel.wire_hop"
KERNEL_DIGEST_ROWS = "kernel.digest_rows"
KERNEL_QGEMM = "kernel.qgemm"
KERNEL_FUSED_GATHER_ATTENTION = "kernel.fused_gather_attention"
KERNEL_FLASH_GQA_FWD = "kernel.flash_gqa_fwd"
KERNEL_FLASH_GQA_BWD_DQ = "kernel.flash_gqa_bwd_dq"
KERNEL_FLASH_GQA_BWD_DKV = "kernel.flash_gqa_bwd_dkv"

KERNELS = (KERNEL_QUANTIZE, KERNEL_QUANTIZE_ADD, KERNEL_QUANTIZE_ADD_SR,
           KERNEL_QUANTIZE_SR, KERNEL_WIRE_HOP, KERNEL_DIGEST_ROWS,
           KERNEL_QGEMM, KERNEL_FUSED_GATHER_ATTENTION,
           KERNEL_FLASH_GQA_FWD, KERNEL_FLASH_GQA_BWD_DQ,
           KERNEL_FLASH_GQA_BWD_DKV)


def kernel_name(scope: str) -> str:
    """`kernel.flash_gqa_fwd` -> `flash_gqa_fwd`: the `name=` of the
    `pl.pallas_call` that the scope surrounds."""
    if not scope.startswith(KERNEL_PREFIX):
        raise ValueError(f"not a kernel scope: {scope!r}")
    return scope[len(KERNEL_PREFIX):]
