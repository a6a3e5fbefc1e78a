"""Pallas quantized-Kahan-accumulator GEMM — the native analog of the
reference's `tvm_gemm` CUDA kernel.

Reference: float_kernel.cu:103-340 — a tiled SGEMM whose inner product is
Kahan-compensated with EVERY intermediate re-cast to eXmY (multiply, y, t,
and the double-cast c; :181-195).  The K dimension is visited strictly in
ascending order, so the semantics are an ordered sequential reduction.

TPU-native design: grid over (M/128, N/128) output tiles; per tile, a
`fori_loop` walks K in order performing a rank-1 (outer-product) update of
the (128,128) accumulator with the quantized Kahan recurrence on the VPU.
The MXU cannot requantize mid-dot — the same fidelity/throughput trade the
reference made by not using tensor cores (SURVEY.md §7.2).  A is passed
transposed (K, M) so the K index walks the sublane dimension, which Mosaic
slices efficiently.

K is never padded: a padded zero step is NOT a Kahan no-op when the
compensation term is nonzero, so zero-padding K would change the numerics.
M/N padding only adds discarded output rows/cols.

Bit-parity: the kernel reuses `cast_body` — the same code as the XLA path —
so `qgemm_pallas == quant_gemm(mode='faithful')` exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from ..compat import pallas as pl, pallas_tpu as pltpu
from ..obs import scopes

from ..quant.numerics import _validate, cast_body

__all__ = ["qgemm_pallas"]

_TILE = 128


def _qgemm_kernel(at_ref, b_ref, o_ref, s_ref, c_ref, *, exp_bits: int,
                  man_bits: int, k_steps: int):
    q = lambda t: cast_body(t, exp_bits, man_bits)
    s_ref[...] = jnp.zeros_like(s_ref)
    c_ref[...] = jnp.zeros_like(c_ref)

    def body(k, _):
        a_col = at_ref[k, :]          # (TILE_M,)
        b_row = b_ref[k, :]           # (TILE_N,)
        tmp = q(a_col[:, None] * b_row[None, :])      # float_kernel.cu:181
        s = s_ref[...]
        c = c_ref[...]
        y = q(tmp - c)                                # :185
        t = q(s + y)                                  # :188
        c_ref[...] = q(q(t - s) - y)                  # :191-194 (double cast)
        s_ref[...] = t
        return 0

    lax.fori_loop(0, k_steps, body, 0)
    o_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def qgemm_pallas(a: jnp.ndarray, b: jnp.ndarray, exp_bits: int,
                 man_bits: int, interpret: bool = False) -> jnp.ndarray:
    """(M,K) @ (K,N) with the quantized-Kahan eXmY accumulator, via Pallas.

    Bit-identical to `quant_gemm(..., mode='faithful')`
    (quant/quant_function.py)."""
    _validate(exp_bits, man_bits)
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"qgemm expects (M,K)x(K,N); got {a.shape} x {b.shape}")
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    m, k = a.shape
    n = b.shape[1]

    mp = -(-m // _TILE) * _TILE
    np_ = -(-n // _TILE) * _TILE
    at = jnp.pad(a.T, ((0, 0), (0, mp - m)))          # (K, Mp)
    bp = jnp.pad(b, ((0, 0), (0, np_ - n)))           # (K, Np)

    call = pl.pallas_call(
        functools.partial(_qgemm_kernel, exp_bits=exp_bits,
                          man_bits=man_bits, k_steps=k),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        grid=(mp // _TILE, np_ // _TILE),
        in_specs=[
            pl.BlockSpec((k, _TILE), lambda i, j: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, _TILE), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_TILE, _TILE), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((_TILE, _TILE), jnp.float32),
            pltpu.VMEM((_TILE, _TILE), jnp.float32),
        ],
        interpret=interpret,
        name=scopes.kernel_name(scopes.KERNEL_QGEMM),
    )
    with jax.named_scope(scopes.KERNEL_QGEMM):
        out = call(at, bp)
    return out[:m, :n]
