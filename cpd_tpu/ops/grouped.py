"""Grouped matrix product over the rows a chip's experts hold.

`grouped_matmul(lhs, rhs, sizes)` multiplies the first `sizes[0]` rows of
`lhs` by `rhs[0]`, the next `sizes[1]` by `rhs[1]`, and so on: the expert
products of a dropless mixture-of-experts layer (`models/mla_moe.py`),
whose row buffer is sized for the worst routing and is mostly empty.  The
kernel is jax's own megablox `gmm` (Pallas, with its `tgmm` backward): its
grid runs over the tiles that hold live rows, so the time follows the
live rows and not the buffer.  Measured on a v5e at 12,288 live rows of
98,304 (2,048 -> 1,408, bf16; PERF.md section 6, PR 30): 0.64 ms here,
1.16 ms for `lax.ragged_dot`, whose TPU lowering also follows the live
rows but drops the operation's name stack, so no scope of a device trace
owns its time.

**Rows past `sum(sizes)` are not written**: on a TPU they hold what the
memory held, in the product and in its gradient with respect to `lhs`
(the CPU's interpreter leaves NaN there).  A caller masks them, before it
multiplies them by anything.

The library's `pallas_call` takes no `name=` from outside, so this kernel
has no `kernel.<name>` scope of its own (obs/scopes.py): the caller's
scope owns it.
"""

from __future__ import annotations

import importlib

import jax.numpy as jnp

from ..compat import megablox_gmm_import

__all__ = ["grouped_matmul"]


def _interpret() -> bool:
    """`ops.backend.interpret_mode()`, asked where `ops/flash_gqa.py` asks
    it: the rehearsal that compiles a whole step for a described TPU from
    the CPU (`benchmark/tests/test_compile_v5e.py`) steers that module's
    answer, and a step's kernels have to be steered together (PERF.md
    section 7: the fixture should steer `ops.backend` itself)."""
    return importlib.import_module(
        ".flash_gqa", __package__).interpret_mode()


_TILE = 512     # rows, contraction and columns of a tile (v5e: 0.64 ms at
                # 512^3 against 6.2 ms at the library's default 128^3)


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   sizes: jnp.ndarray) -> jnp.ndarray:
    """lhs (m, k), rhs (groups, k, n), sizes (groups,) int32 with
    sum(sizes) <= m  ->  (m, n) in lhs.dtype, float32 accumulation."""
    gmm = megablox_gmm_import()
    m, k = lhs.shape
    n = rhs.shape[-1]
    # the row tile has to divide the buffer: the largest power of two
    # that does, up to _TILE
    tm = next(_TILE >> i for i in range(_TILE.bit_length())
              if m % (_TILE >> i) == 0)
    return gmm(lhs, rhs, sizes, lhs.dtype, (tm, min(_TILE, k), min(_TILE, n)),
               None, None, False, _interpret())
