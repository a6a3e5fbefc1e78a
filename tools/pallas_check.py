"""Check every Pallas kernel in the repo as Mosaic compiles it, on the chip.

The unit tests prove the kernels bit-identical to the XLA path under
`interpret=True` on CPU (tests/test_ops_pallas.py); this tool proves the
compiled kernels on a TPU — run it whenever ops/*.py changes:

    python tools/pallas_check.py

It refuses to run on any other backend (`ops.require_tpu`, exit 2):
interpreting here would prove nothing the unit tests do not.  Every check runs in its own try
block and prints ONE status line — OK, MISMATCH with what differed, or
ERROR with the first line of the compiler's message — so one kernel
Mosaic refuses does not hide the others.  Full tracebacks go to
chiprun_out/pallas_check_errors.txt.  Exit 0 only if every line is OK.

The allclose checks compare against the XLA reference computed at
`jax.default_matmul_precision("highest")` with a 2e-2 tolerance (5e-2 for
gradients): on the chip an fp32 matmul at default precision — the
kernels' and XLA's alike — runs as bf16 passes, so the interpret-mode
tolerances of the unit tests (1e-5) do not apply.

Checks (bitwise vs the XLA composition unless noted):
  1. quantize_pallas / quantize_pallas_sr — elementwise eXmY cast
  2. qgemm_pallas — quantized-Kahan-accumulator GEMM
  3. local_attention(impl="flash") — the stock Pallas TPU flash kernel
     vs the reference implementation (allclose)
  4. a transformer Block with attn_impl="flash" vs "xla" (allclose)
  5. chunked attention — pure XLA; cross-checked against 3 on the chip
  6. flash_gqa — forward (incl. a short-Tq case, bq < 128; output and
     lse at the benchmark cells' own shapes and block lengths and at two
     ragged ones; the pad columns of a head of 64 read 0) and its Pallas
     backward, at small shapes and at the cells' own (allclose)
  7. the ring's wire kernels — quantize_add, quantize_pack, hop_pack
     (plain / digest / blocked / multi-tile) and digest_rows: the three
     kernels `--mode ring` selects by default on TPU
  8. fused gather -> unpack -> attention (ServeEngine fused_attn=True)
  9. grouped_matmul (ops/grouped.py: megablox `gmm`/`tgmm`) at the row
     bound of the Moonlight cell's expert layers, forward and both
     gradients against `lax.ragged_dot` on the live rows (allclose)
"""

from __future__ import annotations

import os
import sys
import traceback

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _bits_equal(a, b) -> bool:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype == np.float32:
        a, b = a.view(np.uint32), b.view(np.uint32)
    return a.shape == b.shape and bool(np.array_equal(a, b))


def _close(a, b, tol) -> str:
    """'' when allclose, else the max abs difference as text."""
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    if np.allclose(a, b, atol=tol, rtol=tol):
        return ""
    return f"maxdiff={np.max(np.abs(a - b))}"


def check_quantize(rng):
    import jax.numpy as jnp
    import numpy as np
    from cpd_tpu.ops import quantize_pallas
    from cpd_tpu.quant.numerics import cast_to_format

    bad = []
    for shape in [(7,), (513, 3), (128, 128), (2, 3, 5, 7)]:
        for e, m in [(5, 2), (4, 3), (8, 23)]:
            x = jnp.asarray(rng.randn(*shape).astype(np.float32) * 100)
            if not _bits_equal(quantize_pallas(x, e, m, False),
                               cast_to_format(x, e, m)):
                bad.append(f"{shape} e{e}m{m}")
    return bad


def check_quantize_sr(rng):
    # same bitstream as the XLA path, so the comparison is bitwise even
    # though the rounding is random
    import jax
    import jax.numpy as jnp
    import numpy as np
    from cpd_tpu.ops import quantize_pallas_sr
    from cpd_tpu.quant.numerics import cast_to_format_sr

    bad = []
    for shape in [(513, 3), (256, 128)]:
        for e, m in [(5, 2), (4, 3)]:
            x = jnp.asarray(rng.randn(*shape).astype(np.float32) * 100)
            key = jax.random.PRNGKey(shape[0] + m)
            if not _bits_equal(quantize_pallas_sr(x, e, m, key, False),
                               cast_to_format_sr(x, e, m, key)):
                bad.append(f"{shape} e{e}m{m}")
    return bad


def check_qgemm(rng):
    import jax.numpy as jnp
    import numpy as np
    from cpd_tpu.ops import qgemm_pallas
    from cpd_tpu.quant.quant_function import quant_gemm

    bad = []
    for m, k, n in [(16, 32, 8), (130, 7, 129), (128, 128, 128)]:
        a = jnp.asarray(rng.randn(m, k).astype(np.float32))
        b = jnp.asarray(rng.randn(k, n).astype(np.float32))
        for e, mb in [(5, 10), (8, 23)]:
            if not _bits_equal(
                    qgemm_pallas(a, b, e, mb, False),
                    quant_gemm(a, b, man=mb, exp=e, mode="faithful")):
                bad.append(f"({m},{k},{n}) e{e}m{mb}")
    return bad


def _exact(fn, *args):
    """`fn(*args)` with fp32 matmuls at full precision: the reference."""
    import jax
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def _qkv(rng, b, tq, tk, h, hkv, d):
    import jax.numpy as jnp
    import numpy as np
    return (jnp.asarray(rng.randn(b, tq, h, d).astype(np.float32)),
            jnp.asarray(rng.randn(b, tk, hkv, d).astype(np.float32)),
            jnp.asarray(rng.randn(b, tk, hkv, d).astype(np.float32)))


def check_stock_flash(rng):
    from cpd_tpu.ops.attention import local_attention

    q, k, v = _qkv(rng, 2, 128, 128, 4, 4, 64)
    diff = _close(_exact(lambda: local_attention(q, k, v, causal=True)),
                  local_attention(q, k, v, causal=True, impl="flash"), 2e-2)
    return [diff] if diff else []


def check_flash_block(rng):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from cpd_tpu.models.transformer import Block

    def blk(impl):
        return Block(head_dim=64, d_ff=512, d_model=256, tp_axis=None,
                     sp_axis=None, tp_size=1, dtype=jnp.float32,
                     attn_impl=impl)

    h = jnp.asarray(rng.randn(2, 128, 256).astype(np.float32))
    pos = jnp.arange(128)
    params = blk("xla").init(jax.random.PRNGKey(5), h, pos)
    # both sides at default precision: only the attention differs, the
    # dense layers' bf16-pass error is common to both
    diff = _close(blk("xla").apply(params, h, pos),
                  blk("flash").apply(params, h, pos), 2e-2)
    return [diff] if diff else []


def check_chunked(rng):
    from cpd_tpu.ops.attention import (_chunked_attention,
                                       grouped_query_attention,
                                       local_attention)

    bad = []
    for hkv in (4, 2):
        q, k, v = _qkv(rng, 2, 256, 256, 4, hkv, 64)
        chk = _chunked_attention(q, k, v, True, 0, 0, block=128)
        diff = _close(_exact(lambda: grouped_query_attention(
            q, k, v, causal=True)), chk, 2e-2)
        if diff:
            bad.append(f"hkv={hkv} {diff}")
        if hkv == 4:
            diff = _close(local_attention(q, k, v, causal=True,
                                          impl="flash"), chk, 2e-2)
            if diff:
                bad.append(f"vs stock flash {diff}")
    return bad


def _flash_gqa_fwd(shapes):
    def check(rng):
        from cpd_tpu.ops.attention import grouped_query_attention
        from cpd_tpu.ops.flash_gqa import flash_gqa

        bad = []
        for (tq, tk, h, hkv, d, causal) in shapes:
            q, k, v = _qkv(rng, 2, tq, tk, h, hkv, d)
            diff = _close(flash_gqa(q, k, v, causal),
                          _exact(lambda: grouped_query_attention(
                              q, k, v, causal=causal)), 2e-2)
            if diff:
                bad.append(f"tq={tq} tk={tk} h={h}/{hkv} d={d} "
                           f"causal={causal} {diff}")
        return bad
    return check


# (batch, Tq, Tk, heads, kv heads, D, Dv)
_FWD_CELL_SHAPES = [(2, 8192, 8192, 16, 16, 192, 128),
                    (2, 4096, 4096, 24, 2, 128, 128),
                    (2, 4096, 4096, 16, 16, 128, 128),
                    (2, 8192, 8192, 32, 8, 64, 64),
                    (1, 2500, 3300, 4, 2, 192, 128),
                    (1, 3300, 2500, 24, 2, 128, 128)]


def check_flash_gqa_fwd_cells(rng, shapes=_FWD_CELL_SHAPES):
    """The forward at the benchmark cells' own shapes in bf16, so at the
    block lengths `_fwd_blocks` gives them (the diagonal inside a block
    of 1,024 keys, the steps above it whose copies are skipped), and a
    ragged causal case of several key blocks, with pad rows and pad keys
    that differ in number; output and `lse` against the chunked XLA scan
    and the exact log-sum-exp of the bf16 inputs."""
    import sys

    import jax
    import jax.numpy as jnp
    import numpy as np
    from cpd_tpu.ops.attention import _chunked_attention
    import cpd_tpu.ops.flash_gqa  # noqa: F401  (the attribute is a function)
    fg = sys.modules["cpd_tpu.ops.flash_gqa"]

    def lse_of(q, k):
        """(B, H, Tq) log-sum-exp of the causal scaled scores, a head at
        a time (a (Tq, Tk) float32 block each)."""
        rep = q.shape[2] // k.shape[2]
        row = jnp.arange(q.shape[1])[:, None] >= jnp.arange(k.shape[1])

        def head(qh, kh):
            s = jnp.dot(qh.astype(jnp.float32), kh.astype(jnp.float32).T,
                        precision="highest") / q.shape[-1] ** 0.5
            return jax.nn.logsumexp(jnp.where(row, s, -jnp.inf), axis=-1)
        return jax.jit(lambda q, k: jax.lax.map(
            lambda x: jax.lax.map(lambda y: head(*y), x),
            (q.transpose(0, 2, 1, 3),
             jnp.repeat(k, rep, 2).transpose(0, 2, 1, 3))))(q, k)

    bad = []
    for (bsz, tq, tk, h, hkv, d, dv) in shapes:
        q, k, v = (x.astype(jnp.bfloat16)
                   for x in _qkv(rng, bsz, tq, tk, h, hkv, d))
        v = v[..., :dv]
        out, lse = fg._flash_gqa_fwd_call(q, k, v, True,
                                           fg.interpret_mode())
        want = _chunked_attention(q, k, v, True, 0, 0)
        tag = f"tq={tq} tk={tk} h={h}/{hkv} {d}/{dv} bf16"
        diff = _close(np.asarray(out.astype(jnp.float32)),
                      np.asarray(want.astype(jnp.float32)), 2e-2)
        if diff:
            bad.append(f"{tag} out {diff}")
        got = np.asarray(lse[..., :tq]).reshape(bsz, h, tq)
        diff = _close(got, np.asarray(lse_of(q, k)), 2e-3)
        if diff:
            bad.append(f"{tag} lse {diff}")
    return bad


def check_flash_gqa_pad_columns(rng):
    """A head narrower than 128 lanes reaches the forward kernel padded
    with zero columns (`_q_layout`, `_kv_layout`), and the kernel's output
    columns past the head's width are sliced off: on the chip they must
    read 0, which the interpreter's zeros cannot show.  Inputs handed over
    already 128 wide, zero past column 64, are the arrays the kernel gets
    for heads of 64 (only the softmax scale differs), and their output is
    the kernel's own, every column; LFM2's heads (32 on 8 key heads of
    64), a causal sequence of 2,048 in bf16."""
    import sys

    import jax.numpy as jnp
    import numpy as np
    from cpd_tpu.ops.attention import _chunked_attention
    import cpd_tpu.ops.flash_gqa  # noqa: F401  (the attribute is a function)
    fg = sys.modules["cpd_tpu.ops.flash_gqa"]

    pad = lambda x: jnp.pad(x.astype(jnp.bfloat16),
                            ((0, 0), (0, 0), (0, 0), (0, 64)))
    q, k, v = (pad(x) for x in _qkv(rng, 1, 2048, 2048, 32, 8, 64))
    out, _ = fg._flash_gqa_fwd_call(q, k, v, True, fg.interpret_mode())
    out = np.asarray(out.astype(jnp.float32))
    bad = []
    if np.any(out[..., 64:] != 0):
        bad.append(f"pad columns: {np.count_nonzero(out[..., 64:])} of "
                   f"{out[..., 64:].size} not 0, largest "
                   f"{np.abs(out[..., 64:]).max()}")
    want = np.asarray(_chunked_attention(q, k, v, True, 0, 0).astype(
        jnp.float32))
    diff = _close(out[..., :64], want[..., :64], 2e-2)
    if diff:
        bad.append(f"columns 0-63 {diff}")
    return bad


def _flash_gqa_bwd():
    """The Pallas gradient against the exact XLA one at small float32
    shapes, square and ragged, with a v of its own width, then against the
    chunked XLA gradient at the benchmark cells' own shapes in bf16: the
    padded columns (192 -> 256) and pad rows are what a CPU's zeros can
    hide."""
    def check(rng):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from cpd_tpu.ops.attention import (_chunked_attention,
                                           local_attention)
        from cpd_tpu.ops.flash_gqa import flash_gqa

        def grads(fn, *qkv):
            return jax.jit(jax.grad(
                lambda a, b, c: jnp.sum(jnp.sin(fn(a, b, c).astype(
                    jnp.float32))), argnums=(0, 1, 2)))(*qkv)

        flash = lambda a, b, c: flash_gqa(a, b, c, True)
        bad = []
        # (Tq, Tk, heads, kv heads, D, Dv, causal).  The ragged ones: pad
        # rows of q and of k that differ in number, one block of 40 rows,
        # and at (1,024 x 1,024) blocks four key blocks against two of
        # queries, where a dk/dv step names a q block past the array
        # unless its index is clamped (the interpreter forgives that),
        # then five q blocks against two of keys.  Groups of one or two
        # heads: twelve heads' rows summed into one key head put dk past
        # this tolerance by the bf16 rounding of the inputs alone (1.4
        # times it at 4,000 x 1,500; the chip read maxdiff 0.41 of 172),
        # so a group of twelve is held to the chunked gradient below
        for (tq, tk, h, hkv, d, dv, causal) in [
                (128, 128, 4, 2, 32, 32, True),
                (300, 300, 2, 2, 24, 16, True),
                (130, 100, 4, 2, 32, 32, False),
                (40, 100, 2, 2, 24, 16, False),
                (1500, 4000, 2, 2, 24, 16, True),
                (2500, 1300, 4, 2, 24, 16, True)]:
            q, k, v = _qkv(rng, 1, tq, tk, h, hkv, d)
            v = v[..., :dv]
            want = _exact(grads, lambda a, b, c: local_attention(
                a, jnp.repeat(b, h // hkv, 2), jnp.repeat(c, h // hkv, 2),
                causal=causal), q, k, v)
            got = grads(lambda a, b, c: flash_gqa(a, b, c, causal), q, k, v)
            for name, a, b in zip("qkv", got, want):
                diff = _close(a, b, 5e-2)
                if diff:
                    bad.append(f"tq={tq} tk={tk} {d}/{dv} d{name} {diff}")
        # (batch, Tq, Tk, heads, kv heads, D, Dv): Moonlight's attention
        # a sequence (the chunked gradient of two keeps 8.7 GiB of
        # temporaries), StarCoder2's, the looped LM's (a head of 128 for
        # every key head: the kernels' native width, no group, no
        # padding), LFM2's a sequence (heads of 64 padded to 128, groups
        # of four), and StarCoder2's group of twelve ragged
        for (bsz, t, tk, h, hkv, d, dv) in [
                (1, 8192, 8192, 16, 16, 192, 128),
                (2, 4096, 4096, 24, 2, 128, 128),
                (2, 4096, 4096, 16, 16, 128, 128),
                (1, 8192, 8192, 32, 8, 64, 64),
                (1, 4000, 1500, 24, 2, 128, 128)]:
            q, k, v = (x.astype(jnp.bfloat16)
                       for x in _qkv(rng, bsz, t, tk, h, hkv, d))
            v = v[..., :dv]
            want = grads(lambda a, b, c: _chunked_attention(
                a, b, c, True, 0, 0), q, k, v)
            for name, a, b in zip("qkv", grads(flash, q, k, v), want):
                a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
                diff = _close(a, b, 5e-2)
                if diff:
                    bad.append(f"tq={t} tk={tk} h={h}/{hkv} {d}/{dv} "
                               f"bf16 d{name} {diff}")
        return bad
    return check


def check_quantize_add(rng):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from cpd_tpu.ops.quantize import (quantize_add_pallas,
                                      quantize_add_pallas_bits)
    from cpd_tpu.quant.numerics import cast_body_sr, cast_to_format

    x = jnp.asarray(rng.randn(70000).astype(np.float32))
    y = jnp.asarray(rng.randn(70000).astype(np.float32))
    bad = []
    if not _bits_equal(quantize_add_pallas(x, y, 5, 1, False),
                       cast_to_format(x + y, 5, 1)):
        bad.append("nearest e5m1")
    rbits = jax.random.bits(jax.random.PRNGKey(3), x.shape, jnp.uint32)
    if not _bits_equal(quantize_add_pallas_bits(x, y, 5, 1, rbits, False),
                       cast_body_sr(x + y, 5, 1, rbits)):
        bad.append("stochastic e5m1")
    return bad


def _wire(fmt, block, n, want_digest):
    """quantize_pack (hop 0) then hop_pack (hop 1) on n elements, against
    the XLA composition: values, wire bytes and digest words."""
    e, m = fmt

    def check(rng):
        import jax.numpy as jnp
        import numpy as np
        from cpd_tpu.ops.quantize import (hop_pack_pallas,
                                          quantize_pack_pallas)
        from cpd_tpu.parallel.integrity import wire_digest
        from cpd_tpu.quant.numerics import (cast_body, cast_body_blocked,
                                            pack_exmy, pack_exmy_blocked,
                                            unpack_exmy,
                                            unpack_exmy_blocked)

        g0 = jnp.asarray(rng.randn(n).astype(np.float32) * 0.4)
        g1 = jnp.asarray(rng.randn(n).astype(np.float32) * 0.4)
        if block is None:
            q0 = cast_body(g0, e, m)
            w0 = pack_exmy(q0, e, m)
            q1 = cast_body(unpack_exmy(w0, e, m) + g1, e, m)
            w1 = pack_exmy(q1, e, m)
        else:
            q0 = cast_body_blocked(g0, e, m, block)
            w0 = pack_exmy_blocked(q0, e, m, block)
            q1 = cast_body_blocked(
                unpack_exmy_blocked(w0, e, m, n, block) + g1, e, m, block)
            w1 = pack_exmy_blocked(q1, e, m, block)

        out0 = quantize_pack_pallas(g0, e, m, block_size=block,
                                    want_digest=want_digest)
        out1 = hop_pack_pallas(out0[1], g1, e, m, block_size=block,
                               want_digest=want_digest)
        bad = []
        if not _bits_equal(out0[0], q0):
            bad.append("pack values")
        if not _bits_equal(np.asarray(out0[1]).reshape(-1),
                           np.asarray(w0).reshape(-1)):
            bad.append("pack wire")
        if not _bits_equal(out1[0], q1):
            bad.append("hop values")
        if not _bits_equal(np.asarray(out1[1]).reshape(-1),
                           np.asarray(w1).reshape(-1)):
            bad.append("hop wire")
        if want_digest:
            if int(out0[2]) != int(wire_digest(w0)):
                bad.append("pack digest")
            if (int(out1[2]) != int(wire_digest(w0))
                    or int(out1[3]) != int(wire_digest(w1))):
                bad.append("hop digests")
        return bad
    return check


def check_digest_rows(rng):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from cpd_tpu.ops.quantize import digest_rows_pallas
    from cpd_tpu.parallel.integrity import wire_digest

    bad = []
    for w, nb in [(1, 384), (4, 70000), (8, 1000), (4, 3_000_000)]:
        rows = jnp.asarray(rng.randint(0, 256, size=(w, nb)).astype(np.uint8))
        if not _bits_equal(digest_rows_pallas(rows, False),
                           jax.vmap(wire_digest)(rows)):
            bad.append(f"({w},{nb})")
    return bad


def check_fused_gather_attention(rng):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from cpd_tpu.ops import fused_gather_attention
    from cpd_tpu.serve import kvcache
    from cpd_tpu.serve.kvcache import KVCacheConfig
    from cpd_tpu.serve.model import _paged_attention

    bad = []
    for (h, hkv, d, page, mp, fmt, block) in [
            (4, 2, 8, 4, 3, (4, 3), None),       # GQA 2:1, odd tail page
            (4, 4, 8, 4, 2, (8, 23), None),      # MHA, fp32-exact codec
            (8, 2, 16, 2, 3, (5, 2), None),      # GQA 4:1, tiny pages
            (4, 2, 8, 4, 3, (4, 3), 12),         # blocked, odd blocks
            (8, 8, 64, 16, 16, (5, 2), None)]:   # chip_smoke's serve shape
        cfg = KVCacheConfig(n_layers=1, n_pages=1 + 2 * mp, page_size=page,
                            n_kv_heads=hkv, head_dim=d,
                            exp_bits=fmt[0], man_bits=fmt[1],
                            block_scale=block is not None,
                            block_size=block if block is not None else 32)
        s_count = 2
        kv_raw = jnp.asarray(rng.randn(cfg.n_pages, 2, page, hkv, d)
                             .astype(np.float32))
        pool = kvcache.pack_kv(kv_raw, cfg)[None]    # (1, n_pages, ...)
        rows = jnp.asarray(
            rng.choice(cfg.n_pages, size=(s_count, mp), replace=False)
            .astype(np.int32))
        last = jnp.asarray([mp * page - 2, page + 1], dtype=jnp.int32)
        q = jnp.asarray(rng.randn(s_count, 1, h, d).astype(np.float32))
        pos = last[:, None] + 1
        attn, dig = fused_gather_attention(
            pool[0], q, rows, pos, last, page_size=page,
            unpack_fn=lambda kv, cfg=cfg: kvcache.unpack_kv(kv, cfg),
            attend_fn=_paged_attention, interpret=False)
        k, v = kvcache.gather_kv(pool, 0, rows, cfg)
        tag = f"h={h}/{hkv} d={d} page={page} e{fmt[0]}m{fmt[1]} block={block}"
        if not _bits_equal(attn, _paged_attention(q, k, v, pos, last)):
            bad.append(f"attn {tag}")
        want_dig = jax.vmap(jax.vmap(kvcache.wire_digest))(pool[0][rows])
        if not _bits_equal(dig, want_dig):
            bad.append(f"digests {tag}")
    return bad


def check_grouped_matmul(rng):
    """24,576 rows (`models/mla_moe.py:_row_bound` in the Moonlight cell),
    12,288 of them live in 8 uneven groups, bf16, both of an expert's
    shapes; the rows past the groups are the caller's to mask and are
    left out here."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from cpd_tpu.ops.grouped import grouped_matmul

    rows, live = 24576, 12288
    sizes = np.asarray([2200, 1536, 900, 1536, 1700, 1536, 1344, 1536])
    assert sizes.sum() == live
    sizes = jnp.asarray(sizes, jnp.int32)
    bad = []
    for k, n in [(2048, 1408), (1408, 2048)]:
        x = jnp.asarray(rng.randn(rows, k), jnp.bfloat16)
        w = jnp.asarray(rng.randn(8, k, n) * 0.02, jnp.bfloat16)
        cot = jnp.asarray(rng.randn(live, n), jnp.float32)

        def loss(f):
            return lambda x, w: jnp.sum(
                f(x, w)[:live].astype(jnp.float32) * cot)

        ours = lambda x, w: grouped_matmul(x, w, sizes)
        ref = lambda x, w: jax.lax.ragged_dot(x[:live], w, sizes)
        f32 = lambda a: np.asarray(a.astype(jnp.float32))
        diff = _close(f32(jax.jit(ours)(x, w)[:live]),
                      f32(jax.jit(ref)(x, w)), 2e-2)
        if diff:
            bad.append(f"{k}->{n} forward {diff}")
        grads = lambda f: jax.jit(jax.grad(loss(f), (0, 1)))(x, w)
        for name, a, b in zip(("d lhs", "d rhs"), grads(ours), grads(ref)):
            # a gradient is held to 5e-2 of the LARGEST element, as bf16
            # sums of 1,536 terms in another order differ by an ulp (of
            # d lhs the live rows: the others are the caller's to mask)
            a, b = (f32(g[:live] if name == "d lhs" else g) for g in (a, b))
            scale = float(np.abs(b).max())
            diff = _close(a / scale, b / scale, 5e-2)
            if diff:
                bad.append(f"{k}->{n} {name} {diff} of the largest")
    return bad


def checks() -> list:
    """(status-line name, check function) in run order."""
    out = [
        ("quantize_pallas", check_quantize),
        ("quantize_pallas_sr", check_quantize_sr),
        ("qgemm_pallas", check_qgemm),
        ("stock flash_attention", check_stock_flash),
        ("Block attn_impl=flash", check_flash_block),
        ("chunked attention (XLA)", check_chunked),
        ("flash_gqa fwd", _flash_gqa_fwd(
            [(256, 256, 4, 2, 64, True), (130, 100, 8, 2, 64, False),
             (128, 128, 4, 4, 128, True)])),
        # Tq < 128 shrinks the q block (bq = 8, 40): the lse output's
        # lane dimension is bq
        ("flash_gqa fwd short-Tq", _flash_gqa_fwd(
            [(8, 128, 4, 2, 64, True), (40, 256, 8, 2, 64, False)])),
        ("flash_gqa fwd cells' shapes", check_flash_gqa_fwd_cells),
        ("flash_gqa fwd pad columns of heads of 64",
         check_flash_gqa_pad_columns),
        ("flash_gqa bwd", _flash_gqa_bwd()),
        ("quantize_add_pallas[_bits]", check_quantize_add),
    ]
    # 384 elements = one kernel tile; 200_000 = four, exercising the
    # grid and the digest's accumulation across steps
    for fmt in [(5, 2), (4, 3), (5, 7)]:
        for block in (None, 128):
            for n in (384, 200_000):
                for dig in (False, True):
                    name = (f"wire pack+hop e{fmt[0]}m{fmt[1]} "
                            f"block={block} n={n}"
                            + (" +digest" if dig else ""))
                    out.append((name, _wire(fmt, block, n, dig)))
    out += [("digest_rows_pallas", check_digest_rows),
            ("fused_gather_attention", check_fused_gather_attention),
            ("grouped_matmul 24,576 rows", check_grouped_matmul)]
    return out


def main() -> int:
    import jax
    import numpy as np

    from cpd_tpu.ops import require_tpu
    from cpd_tpu.utils import enable_compile_cache

    dev = require_tpu("pallas_check")[0]
    enable_compile_cache()
    print(f"device: {dev.device_kind} x{len(jax.devices())} "
          f"(jax {jax.__version__})", flush=True)

    rng = np.random.RandomState(0)
    errors = {}
    n_bad = 0
    todo = checks()
    for name, fn in todo:
        try:
            bad = fn(rng)
        except Exception as e:  # noqa: BLE001 — the report boundary: one
            # kernel's Mosaic error must not hide the other kernels' lines
            errors[name] = traceback.format_exc()
            lines = [ln.strip() for ln in str(e).splitlines() if ln.strip()]
            status = (f"ERROR {type(e).__name__}: "
                      f"{lines[0][:300] if lines else ''}")
        else:
            status = "OK" if not bad else "MISMATCH " + "; ".join(bad)
        n_bad += status != "OK"
        print(f"{name}: {status}", flush=True)

    if errors:
        out_dir = os.path.join(_REPO, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "pallas_check_errors.txt"),
                  "w") as f:
            for name, tb in errors.items():
                f.write(f"==== {name}\n{tb}\n")
    print(f"pallas_check: {n_bad} of {len(todo)} checks not OK on "
          f"{dev.device_kind}", flush=True)
    return 1 if n_bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
