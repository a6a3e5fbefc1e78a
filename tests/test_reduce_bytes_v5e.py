"""Hold the byte count of the one-rank gradient reduction, by compiling the
benchmark's LM step for a described `v5e:2x2` (the `on-chip-measurement`
guide's third rehearsal, as `benchmark/tests/test_compile_v5e.py` makes it).

The e5m2-APS twin and its fp32 control differ only in the gradient pipeline,
so the difference of XLA's own `bytes accessed` is what the pipeline moves.
With the leaf kept in its shape and no codec at one rank that is the one
write and read of the gradient tree APS cannot avoid (a leaf's maximum must
be known before any element is scaled); a flattening, a pack or an unpack
that comes back shows as tens of GB.  Nothing runs: a count, not a time.

Marked `slow`: it loads the TPU's library, which one process at a time may
do, and compiles two 535 M-parameter steps (minutes).

    python -m pytest tests/test_reduce_bytes_v5e.py -m slow -q -s
"""

from __future__ import annotations

import re
import sys

import pytest

# the rehearsal's fixtures (the described topology, compiled kernels, a
# step that can be lowered) and its compile, as the benchmark has them
from benchmark.tests.test_compile_v5e import (  # noqa: F401
    compile_cell, compiled_kernels, lowerable_lm_step, topo)
from cpd_tpu.obs import scopes

pytestmark = pytest.mark.slow

TWIN, CONTROL = "starcoder2_3b_aps_e5m2_1chip", "starcoder2_3b_fp32_1chip"
GAP_LIMIT_GB = 10.0      # 48.7 before the one-rank path, 6.5 with it


def compile_step(cell: str, topo):
    """The cell's step compiled for a described chip, with the bucket cap
    given so that the trace takes the TPU's bucketed path (`bucket`
    defaults to on only where the backend is a TPU)."""
    from benchmark import run
    from cpd_tpu.parallel.dist import _BUCKET_ELEMS

    found = dict(run.discover()[cell])
    reduce = found["traffic"]["reduce"]
    if reduce["mode"] == "faithful":
        found["traffic"] = {**found["traffic"], "reduce": {
            **reduce, "bucket_elems": _BUCKET_ELEMS}}
    return compile_cell(found, topo)


def census(compiled) -> dict:
    """XLA's byte count and the entry computation's operations by kind."""
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    ops = re.findall(r"^\s+(?:ROOT )?%?[\w.-]+ = \S+ ([\w-]+)\(", entry,
                     re.MULTILINE)
    return {"gbytes": cost["bytes accessed"] / 1e9,
            "fusions": ops.count("fusion"), "copies": ops.count("copy"),
            "reshapes": ops.count("reshape"),
            "op_names": re.findall(r'op_name="([^"]+)"', text)}


def test_one_rank_pipeline_moves_little_more_than_the_control(
        topo, compiled_kernels, lowerable_lm_step, capsys):
    counts = {cell: census(compile_step(cell, topo))
              for cell in (TWIN, CONTROL)}
    gap = counts[TWIN]["gbytes"] - counts[CONTROL]["gbytes"]
    with capsys.disabled():
        for cell, c in counts.items():
            print(f"\n{cell}: {c['gbytes']:.2f} GB accessed a step, "
                  f"{c['fusions']} fusions, {c['copies']} copy, "
                  f"{c['reshapes']} reshape in the entry computation",
                  file=sys.stderr)
        print(f"twin minus control: {gap:.2f} GB (limit {GAP_LIMIT_GB})",
              file=sys.stderr)
    names = counts[TWIN]["op_names"]
    assert any(scopes.REDUCE_LOCAL in n for n in names)
    wired = sorted({n for n in names
                    if scopes.WIRE_PACK in n or scopes.WIRE_UNPACK in n})
    assert not wired, wired[:5]
    assert 0 < gap < GAP_LIMIT_GB


# ---- the flash kernels' block lengths (ops/flash_gqa.py) ------------------
# here because this is the file of tests/ that loads the TPU's library

@pytest.mark.parametrize("b,t,h,hkv,d,dv,dtype", [
    (2, 8192, 16, 16, 192, 128, "bfloat16"),    # the Moonlight cell's call
    (2, 4096, 24, 2, 128, 128, "bfloat16"),     # the StarCoder2 cells'
    (1, 4096, 8, 2, 256, 256, "float32"),       # wide float32 heads and
    (1, 4096, 32, 1, 192, 128, "float32"),      # a group of 32: these two
                                                # need `_VMEM_LIMIT`
    (1, 4096, 16, 1, 512, 512, "bfloat16"),     # the widest a step gets
    (1, 300, 2, 2, 24, 16, "float32"),          # a multiple of no block
])
def test_flash_kernels_compile_for_v5e_at_their_own_block_lengths(
        topo, b, t, h, hkv, d, dv, dtype):
    """`_fwd_blocks` and `_bwd_blocks` cap a step's scores and count no
    bytes; Mosaic refuses here, as on the chip, a step that does not fit
    the kernels' VMEM limit (at Mosaic's own 16 MiB the two float32
    shapes do not)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    import cpd_tpu.ops.flash_gqa  # noqa: F401  (the attribute is a function)
    fg = sys.modules["cpd_tpu.ops.flash_gqa"]
    one = SingleDeviceSharding(topo.devices[0])
    shaped = lambda *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    q, k, v = shaped(b, t, h, d), shaped(b, t, hkv, d), shaped(b, t, hkv, dv)
    fwd = jax.jit(lambda *a: fg._flash_gqa_fwd_call(*a, True, False))
    out, lse = jax.eval_shape(fwd, q, k, v)
    assert out.shape == (b, t, h, dv)
    fwd.lower(q, k, v).compile()
    out = shaped(*out.shape)
    lse = jax.ShapeDtypeStruct(lse.shape, lse.dtype, sharding=one)
    jax.jit(lambda *a: fg._flash_gqa_bwd_call(*a, True, False)).lower(
        q, k, v, out, lse, out).compile()
