"""Pallas kernels vs the XLA/jnp implementations (interpret mode on CPU).

The kernels share `cast_body` with the XLA path, so equality must be exact
(bitwise), not approximate — these tests assert that.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from cpd_tpu.ops import qgemm_pallas, quantize_pallas
from cpd_tpu.quant import quant_gemm
from cpd_tpu.quant.numerics import cast_to_format

FORMATS = [(5, 2), (4, 3), (8, 23), (2, 0), (8, 0), (1, 10)]


def _rand(shape, seed=0, scale=4.0):
    rng = np.random.RandomState(seed)
    return (rng.randn(*shape) * scale).astype(np.float32)


@pytest.mark.parametrize("exp,man", FORMATS)
def test_quantize_pallas_bitwise_matches_xla(exp, man):
    x = _rand((300, 77), seed=exp * 10 + man)
    got = quantize_pallas(x, exp, man, True)
    want = cast_to_format(x, exp, man)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_quantize_pallas_special_values():
    x = np.array([np.inf, -np.inf, np.nan, 0.0, -0.0, 1e-45, -1e-45,
                  65536.0, 61440.0], np.float32)
    got = np.asarray(quantize_pallas(x, 5, 2, True))
    want = np.asarray(cast_to_format(x, 5, 2))
    np.testing.assert_array_equal(got, want)


def test_quantize_pallas_odd_sizes_and_ranks():
    for shape in [(1,), (129,), (7, 3, 5), (1000,)]:
        x = _rand(shape, seed=sum(shape))
        got = quantize_pallas(x, 4, 3, True)
        want = cast_to_format(x, 4, 3)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("exp,man", [(5, 2), (4, 3), (8, 23)])
def test_qgemm_pallas_bitwise_matches_scan(exp, man):
    a = _rand((24, 17), seed=1, scale=1.0)
    b = _rand((17, 9), seed=2, scale=1.0)
    got = qgemm_pallas(a, b, exp, man, True)
    want = quant_gemm(a, b, man=man, exp=exp, mode="faithful")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_qgemm_pallas_tile_boundary():
    # M, N exactly at and above the 128 tile edge
    a = _rand((128, 5), seed=3, scale=1.0)
    b = _rand((5, 130), seed=4, scale=1.0)
    got = qgemm_pallas(a, b, 5, 2, True)
    want = quant_gemm(a, b, man=2, exp=5, mode="faithful")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_qgemm_pallas_order_sensitivity_preserved():
    """The ordered low-precision accumulation is order-sensitive; the kernel
    must reproduce the forward-order result, not a tree reduction."""
    a = np.array([[1.0, 1e4, -1e4]], np.float32)
    b = np.ones((3, 1), np.float32)
    got = float(qgemm_pallas(a, b, 5, 2, True)[0, 0])
    want = float(quant_gemm(a, b, man=2, exp=5, mode="faithful")[0, 0])
    assert got == want


# ---------------------------------------------------------------------------
# GQA-native flash attention (ops/flash_gqa.py) — interpret mode on CPU;
# tools/pallas_check.py proves the same comparisons on real Mosaic.

import jax  # noqa: E402


@pytest.mark.parametrize("b,tq,tk,h,hkv,d,causal", [
    (2, 256, 256, 4, 2, 64, True),     # GQA, square, causal
    (1, 130, 100, 8, 2, 64, False),    # ragged Tq/Tk (padding paths)
    (2, 128, 128, 4, 4, 128, True),    # rep == 1 (plain MHA)
    (1, 64, 192, 6, 3, 32, True),      # Tq < Tk, D below the lane width
])
def test_flash_gqa_matches_oracle(b, tq, tk, h, hkv, d, causal):
    from cpd_tpu.ops.attention import grouped_query_attention
    from cpd_tpu.ops.flash_gqa import flash_gqa

    rng = np.random.RandomState(tq + h + d)
    q = jnp.asarray(rng.randn(b, tq, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, tk, hkv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, tk, hkv, d).astype(np.float32))
    got = np.asarray(flash_gqa(q, k, v, causal))
    want = np.asarray(grouped_query_attention(q, k, v, causal=causal))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


def test_flash_gqa_matches_chunked():
    """The verdict's bar: agreement with the pure-XLA online-softmax scan
    (same recurrence, different engine)."""
    from cpd_tpu.ops.attention import _chunked_attention
    from cpd_tpu.ops.flash_gqa import flash_gqa

    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(2, 256, 4, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 256, 2, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 256, 2, 64).astype(np.float32))
    got = np.asarray(flash_gqa(q, k, v, True))
    want = np.asarray(_chunked_attention(q, k, v, True, 0, 0, block=128))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


def _exact_attention(q, k, v, causal):
    """Plain softmax attention on expanded K/V: the exact XLA gradient's
    function (`local_attention` takes a v of any width)."""
    from cpd_tpu.ops.attention import local_attention
    rep = q.shape[2] // k.shape[2]
    return local_attention(q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2),
                           causal=causal)


def _assert_flash_grads(q, k, v, causal):
    """`jax.grad` through `flash_gqa` against `jax.grad` through
    `_chunked_attention` and against the exact XLA gradient."""
    from cpd_tpu.ops.attention import _chunked_attention
    from cpd_tpu.ops.flash_gqa import flash_gqa

    def grads(fn):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
            fn(q, k, v).astype(jnp.float32))), argnums=(0, 1, 2))(q, k, v)

    gp = grads(lambda q, k, v: flash_gqa(q, k, v, causal))
    gc = grads(lambda q, k, v: _chunked_attention(q, k, v, causal, 0, 0))
    gx = grads(lambda q, k, v: _exact_attention(q, k, v, causal))
    # float32: the three read within 2e-6 of each other at these shapes.
    # bf16: p and ds are rounded to 8 bits before their products in all
    # three, at different places
    tol = 2e-5 if q.dtype == jnp.float32 else 6e-2
    for a, b_, c in zip(gp, gc, gx):
        assert a.shape == b_.shape and a.dtype == q.dtype
        a = np.asarray(a.astype(jnp.float32))
        np.testing.assert_allclose(a, np.asarray(b_.astype(jnp.float32)),
                                   rtol=tol, atol=tol)
        np.testing.assert_allclose(a, np.asarray(c.astype(jnp.float32)),
                                   rtol=tol, atol=tol)


def _qkv(tq, tk, h, hkv, d, dv, dtype=jnp.float32, seed=9):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (1, tq, h, d)).astype(dtype),
            jax.random.normal(ks[1], (1, tk, hkv, d)).astype(dtype),
            jax.random.normal(ks[2], (1, tk, hkv, dv)).astype(dtype))


_WIDTHS = {"rep12": (12, 1, 16, 16), "v_narrower": (2, 2, 24, 16),
           "v_wider": (4, 2, 16, 24)}
_FWD_WIDTHS = {**_WIDTHS, "rep1": (2, 2, 16, 16)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("widths", list(_WIDTHS))
def test_flash_gqa_grad_matches_chunked_and_exact(widths, causal, dtype):
    """The two Pallas flash-backward kernels (dq with K innermost; fused
    dk/dv with Q innermost, the GQA group sums inside the (rep, bq)
    contractions; each width padded on its own) at a length (300) that is
    a multiple of no block."""
    _assert_flash_grads(*_qkv(300, 300, *_WIDTHS[widths], dtype), causal)


@pytest.mark.parametrize("tq,tk,causal,widths,dtype", [
    (130, 100, False, "v_narrower", jnp.float32),
    (130, 100, False, "rep12", jnp.bfloat16),
    (40, 100, False, "v_narrower", jnp.float32),   # Tq < 128: one block
    (40, 100, False, "v_wider", jnp.bfloat16),     # of ceil8(Tq) rows
    (64, 192, True, "v_wider", jnp.float32),
    (200, 72, True, "v_narrower", jnp.float32),
])
def test_flash_gqa_grad_ragged_lengths(tq, tk, causal, widths, dtype):
    """Tq != Tk: bq comes from Tq and bk from Tk, the forward's lse is cut
    to Tq and padded to the backward's own block, and q's pad rows and
    k's pad rows differ in number."""
    _assert_flash_grads(*_qkv(tq, tk, *_WIDTHS[widths], dtype), causal)


@pytest.mark.parametrize("tq,tk,bq,bk,causal", [
    (984, 984, 128, 128, True),
    (1112, 1112, 128, 256, True),
    (1240, 1240, 256, 128, True),
    (130, 520, 128, 128, True),    # key blocks past every query row: the
    (200, 900, 128, 256, True),    # dk/dv maps' clamp to the last q block
    (520, 130, 128, 128, True),
    (300, 200, 128, 128, False),
    (40, 300, 40, 128, False),
])
def test_flash_gqa_backward_over_many_blocks(monkeypatch, tq, tk, bq, bk,
                                             causal):
    """At the lengths `_bwd_blocks` picks, a CPU-sized sequence is one
    block; with short ones handed in, the causal steps that are skipped
    and those whose copies are skipped too all run, square and ragged."""
    import sys
    from cpd_tpu.ops.flash_gqa import flash_gqa
    fg = sys.modules["cpd_tpu.ops.flash_gqa"]

    # (the lengths are read when the call is traced: no other test has
    # these shapes, so none meets this one's entries in the jit's cache)
    q, k, v = _qkv(tq, tk, 2, 1, 16, 8, seed=4)

    def grads(fn):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v))),
                        argnums=(0, 1, 2))(q, k, v)

    want = grads(lambda q, k, v: _exact_attention(q, k, v, causal))
    monkeypatch.setattr(fg, "_bwd_blocks", lambda *a: (bq, bk))
    got = grads(lambda q, k, v: flash_gqa(q, k, v, causal))
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-5, atol=2e-5)


def test_flash_gqa_backward_block_lengths():
    """The rule's choices at the benchmark's two calls (timed on the
    chip, PERF.md section 6, PR 31), at short and ragged sequences, and
    at a large group."""
    import sys
    import cpd_tpu.ops.flash_gqa  # noqa: F401
    blocks = sys.modules["cpd_tpu.ops.flash_gqa"]._bwd_blocks

    assert blocks(1, 8192, 8192) == (1024, 1024)
    assert blocks(12, 4096, 4096) == (128, 512)
    assert blocks(2, 300, 300) == (512, 512)
    assert blocks(2, 130, 100) == (256, 128)
    assert blocks(2, 40, 100) == (40, 128)
    assert blocks(4, 4096, 4096) == (256, 1024)
    assert blocks(32, 4096, 4096) == (128, 256)


@pytest.mark.parametrize("tq,tk,bq,bk", [
    (8192, 8192, 1024, 1024), (4096, 4096, 128, 512), (130, 520, 128, 128),
    (200, 900, 128, 256), (520, 130, 128, 128), (1500, 4000, 1024, 1024)])
def test_flash_gqa_backward_causal_steps_name_blocks_in_range(tq, tk, bq,
                                                              bk):
    """The interpreter forgives a block index past the array, the chip
    does not: every step of both causal grids names a block that exists,
    and a step that computes names its own."""
    import sys
    import cpd_tpu.ops.flash_gqa  # noqa: F401
    fg = sys.modules["cpd_tpu.ops.flash_gqa"]

    n_q, n_k = -(-tq // bq), -(-tk // bk)
    i, j = np.meshgrid(np.arange(n_q), np.arange(n_k), indexing="ij")
    computes = j * bk <= i * bq + (bq - 1)       # the kernels' own test
    kj = np.asarray(fg._dq_k_block(i, j, bq, bk))
    qi = np.asarray(fg._dkv_q_block(j, i, bq, bk, n_q))
    assert kj.min() >= 0 and kj.max() < n_k
    assert qi.min() >= 0 and qi.max() < n_q
    np.testing.assert_array_equal(kj[computes], j[computes])
    np.testing.assert_array_equal(qi[computes], i[computes])
    # a skipped step names the block of the nearest step that computes
    assert (np.diff(kj, axis=1) >= 0).all()
    assert (np.diff(qi, axis=0) >= 0).all()


def _exact_lse(q, k, causal):
    """Log-sum-exp of each query row's scaled scores, per head:
    (B, H, Tq)."""
    rep = q.shape[2] // k.shape[2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, 2),
                   precision="highest") / q.shape[-1] ** 0.5
    if causal:
        s = jnp.where(jnp.arange(q.shape[1])[:, None]
                      >= jnp.arange(k.shape[1])[None], s, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1)


@pytest.mark.parametrize("tq,tk,bq,bk,causal,widths", [
    (976, 976, 128, 128, True, "rep1"),       # square, the diagonal at
    (1104, 1104, 128, 256, True, "rep12"),    # every offset in a block
    (1232, 1232, 256, 128, True, "v_narrower"),
    (136, 528, 128, 128, True, "v_wider"),    # keys past every query row
    (208, 912, 128, 256, True, "rep1"),
    (528, 136, 128, 128, True, "rep12"),      # rows past every key
    (296, 208, 128, 128, False, "v_narrower"),
    (48, 296, 48, 128, False, "rep1"),        # one block of all the rows
    (392, 392, 128, 256, False, "rep12"),
])
def test_flash_gqa_forward_over_many_blocks(monkeypatch, tq, tk, bq, bk,
                                            causal, widths):
    """At the lengths `_fwd_blocks` picks, a CPU-sized sequence is one
    step; with short ones handed in, the steps that compute, the causal
    steps that are skipped and those whose copies are skipped too all
    run: the output against the exact attention, `lse` against the exact
    log-sum-exp."""
    import sys
    import cpd_tpu.ops.flash_gqa  # noqa: F401
    fg = sys.modules["cpd_tpu.ops.flash_gqa"]

    q, k, v = _qkv(tq, tk, *_FWD_WIDTHS[widths], seed=5)
    monkeypatch.setattr(fg, "_fwd_blocks", lambda *a: (bq, bk))
    # (the jitted call would serve another test's trace of these shapes,
    # made at other lengths)
    out, lse = fg._flash_gqa_fwd_call.__wrapped__(q, k, v, causal, True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_exact_attention(q, k, v, causal)),
        rtol=2e-6, atol=2e-6)
    assert lse.shape[-1] == -(-tq // bq) * bq
    h = q.shape[2]
    np.testing.assert_allclose(
        np.asarray(lse[..., :tq]).reshape(1, h, tq),
        np.asarray(_exact_lse(q, k, causal)), rtol=2e-6, atol=2e-6)


def test_flash_gqa_forward_fully_masked_row(monkeypatch):
    """A row none of whose keys counts (every score -inf) reads o = 0 and
    an lse of -1e30, in a causal step where most of its block is masked
    besides: p is zeroed by the mask, not by exp(-1e30 - m), which is 1
    while the row's maximum is still -1e30."""
    import sys
    import cpd_tpu.ops.flash_gqa  # noqa: F401
    fg = sys.modules["cpd_tpu.ops.flash_gqa"]

    q, k, v = _qkv(264, 264, 2, 1, 16, 8, seed=6)
    k = jnp.abs(k) + 0.5
    dead = 5
    q = q.at[0, dead].set(-3e38)
    monkeypatch.setattr(fg, "_fwd_blocks", lambda *a: (128, 128))
    out, lse = fg._flash_gqa_fwd_call.__wrapped__(q, k, v, True, True)
    out, lse = np.asarray(out), np.asarray(lse)[0, 0, :, :264]
    assert (out[0, dead] == 0).all() and (lse[:, dead] < -9e29).all()
    live = np.arange(264) != dead
    np.testing.assert_allclose(
        out[0, live], np.asarray(_exact_attention(q, k, v, True))[0, live],
        rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(
        lse[:, live], np.asarray(_exact_lse(q, k, True))[0][:, live],
        rtol=2e-6, atol=2e-6)


def test_flash_gqa_forward_block_lengths():
    """The forward rule's choices at the benchmark's two calls (timed on
    the chip, PERF.md section 6, PR 33), at short and ragged sequences,
    and at large groups: the backward's rows, and keys within a cap
    twice the backward's."""
    import sys
    import cpd_tpu.ops.flash_gqa  # noqa: F401
    blocks = sys.modules["cpd_tpu.ops.flash_gqa"]._fwd_blocks

    assert blocks(1, 8192, 8192) == (1024, 1024)
    assert blocks(12, 4096, 4096) == (128, 1024)
    assert blocks(2, 300, 300) == (512, 512)
    assert blocks(2, 130, 100) == (256, 128)
    assert blocks(2, 40, 100) == (40, 128)
    assert blocks(4, 4096, 4096) == (256, 1024)
    assert blocks(16, 4096, 4096) == (128, 1024)
    assert blocks(32, 4096, 4096) == (128, 512)


@pytest.mark.parametrize("rep,tq,tk", [
    (1, 8192, 8192), (12, 4096, 4096), (2, 1500, 4000), (4, 2500, 1300)])
def test_flash_gqa_forward_causal_steps_name_blocks_in_range(rep, tq, tk):
    """The forward's K/V map at the rule's own lengths (its grid is the
    dq kernel's): every step names a block that exists, a step that
    computes names its own, and a skipped step the last one that did."""
    import sys
    import cpd_tpu.ops.flash_gqa  # noqa: F401
    fg = sys.modules["cpd_tpu.ops.flash_gqa"]

    bq, bk = fg._fwd_blocks(rep, tq, tk)
    n_q, n_k = -(-tq // bq), -(-tk // bk)
    i, j = np.meshgrid(np.arange(n_q), np.arange(n_k), indexing="ij")
    computes = j * bk <= i * bq + (bq - 1)       # the kernel's own test
    kj = np.asarray(fg._dq_k_block(i, j, bq, bk))
    assert kj.min() >= 0 and kj.max() < n_k
    np.testing.assert_array_equal(kj[computes], j[computes])
    last = np.where(computes, j, -1).max(axis=1, keepdims=True)
    np.testing.assert_array_equal(kj[~computes],
                                  np.broadcast_to(last, kj.shape)[~computes])


def test_flash_gqa_routing_and_validation():
    """grouped_query_attention(impl='flash') routes GQA to the native
    kernel (no expansion error), rejects offsets and bad head ratios."""
    from cpd_tpu.ops.attention import grouped_query_attention
    from cpd_tpu.ops.flash_gqa import flash_gqa

    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(1, 64, 4, 32).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 64, 2, 32).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 64, 2, 32).astype(np.float32))
    got = np.asarray(grouped_query_attention(q, k, v, causal=True,
                                             impl="flash"))
    want = np.asarray(grouped_query_attention(q, k, v, causal=True))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    with pytest.raises(ValueError, match="offset"):
        grouped_query_attention(q, k, v, causal=True, q_offset=4,
                                impl="flash")
    with pytest.raises(ValueError, match="multiple"):
        flash_gqa(q, k[:, :, :1].repeat(3, axis=2), v[:, :, :1].repeat(
            3, axis=2), True)


# ---------------------------------------------------------------------------
# Fused wire kernels (ISSUE 9): one Pallas pass = unpack + accumulate +
# (block-)scale + quantize + pack + Fletcher digest.  Every stage shares
# its un-jitted body with the XLA path, so parity is BITWISE — values,
# wire bytes, AND digest words.
# ---------------------------------------------------------------------------

import jax  # noqa: E402

from cpd_tpu.ops.quantize import (fletcher_mod65521,  # noqa: E402
                                  hop_pack_pallas, quantize_pack_pallas)
from cpd_tpu.parallel.integrity import (digest_concat,  # noqa: E402
                                        wire_digest)
from cpd_tpu.quant.numerics import (cast_body_blocked,  # noqa: E402
                                    pack_exmy, pack_exmy_blocked,
                                    sr_bits_at, unpack_exmy,
                                    unpack_exmy_blocked)


def test_fletcher_mod65521_matches_modulo():
    rng = np.random.RandomState(0)
    x = jnp.asarray(np.concatenate([
        rng.randint(0, 2 ** 32, 4096, np.uint64),
        [0, 1, 65520, 65521, 65522, 2 ** 32 - 1, 2 ** 16, 2 ** 16 - 1],
    ]).astype(np.uint32))
    got = np.asarray(fletcher_mod65521(x))
    np.testing.assert_array_equal(got, np.asarray(x) % np.uint32(65521))


def _wire_xla(g, prev_wire, exp, man, rbits=None, block=None):
    """The XLA composition of one hop — the reference the kernel must
    match byte-for-byte."""
    n = g.size
    if prev_wire is None:
        s = g
    else:
        if block is None:
            prev = unpack_exmy(prev_wire, exp, man)
        else:
            prev = unpack_exmy_blocked(prev_wire, exp, man, n, block)
        s = prev + g
    if block is None:
        from cpd_tpu.quant.numerics import cast_body, cast_body_sr
        q = (cast_body(s, exp, man) if rbits is None
             else cast_body_sr(s, exp, man, rbits))
        return q, pack_exmy(q, exp, man)
    q = cast_body_blocked(s, exp, man, block,
                          rbits=rbits)
    return q, pack_exmy_blocked(q, exp, man, block)


@pytest.mark.parametrize("exp,man", [(5, 2), (4, 3), (5, 7)])
@pytest.mark.parametrize("sr", [False, True])
@pytest.mark.parametrize("block", [None, 128])
def test_fused_wire_kernels_match_xla_hop(exp, man, sr, block):
    """hop-0 emit and a mid-hop through the fused kernels == the XLA
    composition: partials bitwise, wire bytes identical, digests equal
    `wire_digest` of the full buffers (sidecar included)."""
    n = 300
    rng = np.random.RandomState(exp * 10 + man + (7 if sr else 0))
    g0 = jnp.asarray((rng.randn(n) * 0.4).astype(np.float32))
    g1 = jnp.asarray((rng.randn(n) * 0.4).astype(np.float32))
    key = jax.random.PRNGKey(5)
    offs = jnp.arange(n, dtype=jnp.uint32)
    rb0 = sr_bits_at(jax.random.fold_in(key, 0), offs) if sr else None
    rb1 = sr_bits_at(jax.random.fold_in(key, 1), offs) if sr else None

    res0, wire0, d0 = quantize_pack_pallas(
        g0, exp, man, rbits=rb0, block_size=block, want_digest=True,
        interpret=True)
    q0, w0_ref = _wire_xla(g0, None, exp, man, rbits=rb0, block=block)
    np.testing.assert_array_equal(np.asarray(res0).view(np.uint32),
                                  np.asarray(q0).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(wire0).reshape(-1),
                                  np.asarray(w0_ref).reshape(-1))
    assert int(d0) == int(wire_digest(w0_ref))

    res1, wire1, d_in, d_out = hop_pack_pallas(
        wire0, g1, exp, man, rbits=rb1, block_size=block,
        want_digest=True, interpret=True)
    q1, w1_ref = _wire_xla(g1, w0_ref, exp, man, rbits=rb1, block=block)
    np.testing.assert_array_equal(np.asarray(res1).view(np.uint32),
                                  np.asarray(q1).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(wire1).reshape(-1),
                                  np.asarray(w1_ref).reshape(-1))
    assert int(d_in) == int(wire_digest(w0_ref))
    assert int(d_out) == int(wire_digest(w1_ref))

    # digest-free variant returns the same wire
    res1b, wire1b = hop_pack_pallas(wire0, g1, exp, man, rbits=rb1,
                                    block_size=block, interpret=True)
    np.testing.assert_array_equal(np.asarray(wire1b).reshape(-1),
                                  np.asarray(wire1).reshape(-1))


def test_fused_blocked_rejects_unaligned_block():
    g = jnp.zeros(300, jnp.float32)
    with pytest.raises(ValueError, match="multiple of 128"):
        quantize_pack_pallas(g, 5, 2, block_size=96, interpret=True)


def test_digest_concat_is_concat_digest():
    """digest_concat(d(a), len(a), d(b)) == wire_digest(a ++ b) — the
    identity that lets the kernel digest the code lane and XLA digest
    the sidecar, composing exactly."""
    rng = np.random.RandomState(3)
    for la, lb in ((0, 5), (1, 1), (300, 7), (4096, 129)):
        a = jnp.asarray(rng.randint(0, 256, la, np.int64), jnp.uint8)
        b = jnp.asarray(rng.randint(0, 256, lb, np.int64), jnp.uint8)
        got = digest_concat(wire_digest(a), la, wire_digest(b))
        want = wire_digest(jnp.concatenate([a, b]))
        assert int(got) == int(want), (la, lb)


# ---------------------------------------------------------------- ISSUE 12
def test_digest_rows_pallas_matches_wire_digest():
    """The one-pass per-row digest kernel == vmap(integrity.wire_digest)
    bitwise — tile-boundary shapes, tiny rows, multi-tile rows."""
    from cpd_tpu.ops.quantize import digest_rows_pallas
    from cpd_tpu.parallel.integrity import wire_digest
    rng = np.random.RandomState(0)
    for w, nb in [(8, 37), (4, 4096), (3, 65536 + 17), (1, 1),
                  (2, 131072), (5, 65536)]:
        rows = jnp.asarray(rng.randint(0, 256, size=(w, nb)), jnp.uint8)
        got = digest_rows_pallas(rows, True)
        want = jax.vmap(wire_digest)(rows)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"({w}, {nb})")


def test_digest_rows_pallas_rejects_bad_shapes():
    from cpd_tpu.ops.quantize import digest_rows_pallas
    with pytest.raises(ValueError, match="uint8"):
        digest_rows_pallas(jnp.zeros((4,), jnp.uint8), True)
