"""GQA-native Pallas flash attention — grouped queries against UNEXPANDED
(B, T, H_kv, D) K/V.

The stock `jax.experimental.pallas.ops.tpu.flash_attention` kernel takes
uniform heads only, so the GQA paths either fell back to the pure-XLA
chunked scan or re-materialized the rep× K/V expansion after the Ulysses
all_to_all (round-4 verdict weak #5) — paying in HBM exactly what
`grouped_query_attention` exists to avoid.  This kernel closes that gap:
one (B, H_kv, q-block) program holds ALL `rep` query heads of its kv head
in VMEM and runs the flash online-softmax recurrence against each K/V
block ONCE — K/V HBM traffic is 1/rep of the expanded path's, and nothing
rep-sized is ever materialized anywhere.

The reference (drcut/CPD) has no attention at all (SURVEY.md §5); this is
new-capability code, TPU-first.

Design notes:
  * grid (B, H_kv, Tq/bq, Tk/bk), K innermost; the (o, m, l) accumulator
    lives in VMEM scratch, which persists across the innermost grid steps
    (the standard Pallas TPU flash pattern).  Output is written once, at
    the final K step.
  * the q block is (rep, bq, D): logits are ONE (rep·bq, D)x(D, bk) MXU
    contraction via dot_general — no per-head loop, no reshape.
  * a step takes up to 1,024 keys (`_fwd_blocks`, from the group's size
    and the sequence lengths, as the backward's `_bwd_blocks`): the
    accumulator's and the running maximum's and sum's read-modify-write,
    `alpha`'s exponential and the two cross-lane reductions a row are
    paid once a step, so once per 8 vregs of a row's scores at 1,024
    keys where a 128-key step paid them per vreg (v5e, one call with its
    layout passes: Moonlight's 27.1 -> 9.5 ms, StarCoder2's 4.08 -> 2.56;
    the sweep is in the comment on `_FWD_SCORES`).
  * masking zeroes p directly (p = where(valid, exp(s - m), 0)), so pad
    keys and fully-masked rows contribute 0 to l — a fully-masked row
    yields o = 0 rather than a pad-key average (the degenerate-row edge
    the ADVICE round-4 note flags for `_chunked_attention`).
  * causal K blocks strictly above the diagonal skip their compute via
    `pl.when`, and their copies too: such a step names the block its
    neighbour on the diagonal holds (`_dq_k_block`: the forward's grid
    is the dq kernel's), which Pallas does not fetch again.
  * fp32 logits/softmax; p is cast to the V dtype for the PV matmul —
    the same precision recipe as `_fold_segment` (attention.py).

Backward: `jax.custom_vjp`, the flash-backward recipe on the MXU.  The
forward also emits the per-row LSE, and two kernels — dq (K innermost)
and fused dk/dv (Q innermost, the GQA group-sums folded into (rep, bq)
contractions) — re-exponentiate p = exp(s − lse) per block, at v's own
width and with block lengths of their own (`_bwd_blocks`: the forward's
rule under a cap half as large); causal steps above the diagonal
neither compute nor copy.  What it costs on the
chip beside the XLA gradient of `_chunked_attention` (which
`attn_impl="chunked"` still runs, and the tests hold this one to):
PERF.md section 6, PR 31.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from ..compat import pallas as pl, pallas_tpu as pltpu
from ..obs import scopes

from .attention import _NEG_INF, _gqa_rep  # attention imports us lazily
from .backend import interpret_mode

__all__ = ["flash_gqa", "KEEP_FLASH_RESIDUALS"]

_BQ = 128   # query rows per program and head of the group (pre-rep);
            # MXU/sublane aligned; `_step_blocks` lengthens it for rep < 8


def _flash_gqa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                      m_ref, l_ref, *,
                      causal: bool, scale: float, tk: int,
                      bq: int, bk: int, n_k: int):
    i = pl.program_id(2)          # q block index
    j = pl.program_id(3)          # k block index

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: the whole K block is above the diagonal iff its first key
    # position exceeds the block's last query position
    compute = (j * bk <= i * bq + (bq - 1)) if causal else True

    @pl.when(compute)
    def _():
        q = q_ref[0, 0]           # (rep, bq, D)
        k = k_ref[0, 0]           # (bk, D)
        v = v_ref[0, 0]           # (bk, Dv)
        s = lax.dot_general(
            q, k, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (rep, bq, bk)

        valid = None              # no pad key and no diagonal: no mask
        if causal or n_k * bk != tk:
            qpos = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            valid = kpos < tk                              # pad keys out
            if causal:
                valid = valid & (qpos >= kpos)
            valid = valid[None]                            # (1, bq, bk)
            s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_ref[...]                                # (rep, bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                             # (rep, bq, bk)
        if valid is not None:
            # p is zeroed by the mask, not by exp(-inf): when every key
            # so far is masked m_new is still _NEG_INF and exp(s - m_new)
            # would be 1
            p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                    # (rep, bq, 1)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        pv = lax.dot_general(
            p.astype(v.dtype), v, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (rep, bq, Dv)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)                 # (rep, bq, 1)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # log-sum-exp per row, consumed by the Pallas backward (a
        # fully-masked row keeps lse ~ -1e30; its p re-exponentiates
        # to 0 there via the same validity mask)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l))[..., 0]


def _pad128(d: int) -> int:
    return max(128, -(-d // 128) * 128)


def _q_layout(x, hkv, rep, tq_p, d_p):
    """(B, Tq, H, D) -> padded (B, H_kv, rep, Tq_p, D_p)."""
    b, tq, _, d = x.shape
    return jnp.pad(x.reshape(b, tq, hkv, rep, d).transpose(0, 2, 3, 1, 4),
                   ((0, 0), (0, 0), (0, 0), (0, tq_p - tq),
                    (0, d_p - d)))


def _kv_layout(x, tk_p, d_p):
    """(B, Tk, H_kv, D) -> padded (B, H_kv, Tk_p, D_p)."""
    return jnp.pad(x.transpose(0, 2, 1, 3),
                   ((0, 0), (0, 0), (0, tk_p - x.shape[1]),
                    (0, d_p - x.shape[-1])))


# Block lengths, timed on a v5e for one layer's call in bf16, causal
# (`tools/bench_flash_gqa.py`: the kernels with the layout passes around
# them).
#
# The forward (PERF.md section 6, PR 33).  Moonlight's call (2 x 8,192
# tokens, 16 heads of 192/128 at rep 1; 27.1 ms at PR 32's fixed 128 keys
# a step): (bq, bk) = (1,024, 1,024) 9.51 ms, (2,048, 1,024) 10.24, (512,
# 1,024) 10.25, (1,024, 2,048) 10.49, (512, 512) 12.30, (1,024, 512) 13.07,
# (1,024, 256) 22.17, (1,024, 128) 29.05.  StarCoder2's (2 x 4,096, 24
# heads of 128 at rep 12; 4.08 before): (256, 1,024) 2.53, (128, 1,024)
# 2.56, (128, 2,048) 2.99, (256, 512) 3.06, (128, 512) 3.50, (256, 256)
# 4.77, (128, 256) 5.10, (128, 128) 8.31.  What a step pays once (module
# docstring) makes long steps pay up to 1,024 keys; past that the work a
# crossed block does above the diagonal costs more than the step saves.
# The forward ranks a group of twelve otherwise than the backward pair
# does ((128, 1,024) before (128, 512), the backward's choice), so it has
# a cap of its own, two million scores: the rule takes (1,024, 1,024), the
# best, and (128, 1,024), 1% behind a pair that wants three million.
# Tried in a scratch copy of the kernel and left out (my chip run, PR 33):
# a second body without the mask for the blocks that neither the diagonal
# nor the pad crosses, 8.62 against 8.84 ms at the Moonlight call and
# 2.54 against 2.55 at StarCoder2's, 0.3% of a step; a loop inside the
# step over slices of the keys (12.6 ms at 512 keys, 19.7 at 256) or of
# the rows (9.7 at 256 rows) with the state carried as values; a per-row
# guard on the maximum in place of the second `where` (9.26 against 9.08).
#
# The backward pair (PERF.md section 6, PR 31).  Moonlight's (the chunked
# XLA gradient: 168.1 ms): (1,024, 1,024) 23.8 ms, (1,024, 512) 24.5, (512,
# 512) 25.4, (2,048, 512) 25.6, (512, 256) 31.3, (256, 256) 39.7, (1,024,
# 128) 43.4.  StarCoder2's (chunked 60.7): (256, 512) 5.54, (128, 512) 5.76,
# (256, 256) 6.01, (128, 1,024) 6.21, (128, 256) 6.53, (128, 128) 10.48.  An
# accumulator is read and written once a step, so long steps pay; past a
# million scores a block little or nothing is won.  The rule takes (1,024,
# 1,024), the best, and (128, 512), 4% behind a pair that wants 1.5 million
# scores.
_FWD_SCORES = 2 ** 21          # most scores, rep x bq x bk, a step holds:
_BWD_SCORES = 2 ** 20          # the forward's, and the two backward kernels'
# Mosaic's own limit of 16 MiB a kernel holds the benchmark's two bf16
# backward calls and refuses a million float32 scores (8 heads over 2 of
# 256, a group of 32 at 192/128; `tests/test_reduce_bytes_v5e.py` compiles
# all three kernels at them).  48 MiB is a number for the v5e, whose core
# has 128 MiB of VMEM: a part with less wants a smaller one, and the two
# caps with it
_VMEM_LIMIT = 48 * 2 ** 20


def _fit(block: int, t: int) -> int:
    """`block` halved while half of it still holds all t rows, never
    under the 128 lanes of a score tile."""
    while block > 128 and block // 2 >= t:
        block //= 2
    return block


def _step_blocks(rep, tq, tk, scores):
    """(bq, bk) of a grid step: 1,024 rows of scores over the group's
    heads (a program works on rep x bq rows: a group of few query heads
    takes a longer block, so that a step's cost is spread over about as
    many rows as a wide group's; rep >= 8 keeps `_BQ`) and as many keys,
    1,024 at most, as keep the block within `scores`."""
    bq = _fit(_BQ * max(1, 8 // rep), tq)
    if tq < 128:      # one block of all the rows
        bq = -(-tq // 8) * 8
    bk = _fit(1024, tk)
    while bk > 128 and rep * bq * bk > scores:
        bk //= 2
    return bq, bk


def _fwd_blocks(rep, tq, tk):
    """(bq, bk) of the forward kernel."""
    return _step_blocks(rep, tq, tk, _FWD_SCORES)


def _bwd_blocks(rep, tq, tk):
    """(bq, bk) of both backward kernels."""
    return _step_blocks(rep, tq, tk, _BWD_SCORES)


# Pallas copies whatever block a spec names, for a step whose compute
# `pl.when` skips too.  A causal step above the diagonal names the block
# its neighbour on the diagonal needs, which is then in VMEM already and
# is not copied again (the backward pair: 23.8 against 25.4 ms at the
# Moonlight shape, 5.76 against 6.17 at StarCoder2's; the forward: 9.26
# against 10.23 and, at (128, 512), 4.04 against 4.01: nothing at twelve
# heads a key head, whose steps are long beside a 256 KiB copy)

def _dq_k_block(i, j, bq, bk):
    """The k block that step (q block i, k block j) of the causal forward
    and dq kernels names: j up to the last block q block i's rows reach."""
    return jnp.minimum(j, (i * bq + bq - 1) // bk)


def _dkv_q_block(j, i, bq, bk, n_q):
    """The q block that step (k block j, q block i) of the causal dk/dv
    kernel names: i from the first block whose rows reach k block j (the
    last q block where the keys go on past every query)."""
    return jnp.maximum(i, jnp.minimum(j * bk // bq, n_q - 1))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _flash_gqa_fwd_call(q, k, v, causal: bool, interpret: bool):
    """Returns ((B, Tq, H, Dv) out, (B, H_kv, rep, Tq_p) lse)."""
    b, tq, h, d = q.shape
    tk, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    rep = h // hkv
    d_p, dv_p = _pad128(d), _pad128(dv)     # v's own width (latent
                                            # attention: Dv < D)
    scale = 1.0 / float(d) ** 0.5
    bq, bk = _fwd_blocks(rep, tq, tk)
    tq_p, tk_p = -(-tq // bq) * bq, -(-tk // bk) * bk
    n_q, n_k = tq_p // bq, tk_p // bk
    # layouts: q -> (B, H_kv, rep, Tq, D); k/v -> (B, H_kv, Tk, D).
    # D zero-pad changes no logit (q·k unaffected) and only adds zero
    # output columns, sliced off below; pad keys are masked by position.
    qt = _q_layout(q, hkv, rep, tq_p, d_p)
    kt = _kv_layout(k, tk_p, d_p)
    vt = _kv_layout(v, tk_p, dv_p)

    def kv(width):
        return pl.BlockSpec(
            (1, 1, bk, width),
            (lambda bi, g, i, j: (bi, g, _dq_k_block(i, j, bq, bk), 0))
            if causal else (lambda bi, g, i, j: (bi, g, j, 0)),
            memory_space=pltpu.VMEM)

    call = pl.pallas_call(
        functools.partial(_flash_gqa_kernel, causal=causal, scale=scale,
                          tk=tk, bq=bq, bk=bk, n_k=n_k),
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, rep, tq_p, dv_p), q.dtype),
            jax.ShapeDtypeStruct((b, hkv, rep, tq_p), jnp.float32),
        ),
        grid=(b, hkv, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, rep, bq, d_p),
                         lambda bi, g, i, j: (bi, g, 0, i, 0),
                         memory_space=pltpu.VMEM),
            kv(d_p), kv(dv_p),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, rep, bq, dv_p),
                         lambda bi, g, i, j: (bi, g, 0, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, rep, bq),
                         lambda bi, g, i, j: (bi, g, 0, i),
                         memory_space=pltpu.VMEM),
        ),
        # the running maximum and sum are a column: with 128 lanes of
        # copies a step reads and writes 128 times what it needs
        scratch_shapes=[
            pltpu.VMEM((rep, bq, dv_p), jnp.float32),
            pltpu.VMEM((rep, bq, 1), jnp.float32),
            pltpu.VMEM((rep, bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=scopes.kernel_name(scopes.KERNEL_FLASH_GQA_FWD),
    )
    with jax.named_scope(scopes.KERNEL_FLASH_GQA_FWD):
        out, lse = call(qt, kt, vt)
    # (B, H_kv, rep, Tq_p, Dv_p) -> (B, Tq, H, Dv)
    out = out[:, :, :, :tq, :dv].transpose(0, 3, 1, 2, 4).reshape(
        b, tq, h, dv)
    return out, lse


def _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, i, j, *,
              causal, scale, tk, bq, bk):
    """Shared flash-backward block recompute: (p, ds / scale) for q block
    i vs k block j — the numerically delicate mask/re-exponentiation
    recipe, ONE copy consumed by both backward kernels (only their final
    contractions differ; each scales its accumulator once, at the end)."""
    q = q_ref[0, 0]                                   # (rep, bq, D)
    k = k_ref[0, 0]                                   # (bk, D)
    v = v_ref[0, 0]                                   # (bk, Dv)
    do = do_ref[0, 0]                                 # (rep, bq, Dv)
    lse = lse_ref[0, 0][..., None]                    # (rep, bq, 1)
    delta = delta_ref[0, 0][..., None]                # (rep, bq, 1)
    s = lax.dot_general(
        q, k, (((2,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (rep, bq, bk)
    qpos = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = kpos < tk
    if causal:
        valid = valid & (qpos >= kpos)
    p = jnp.where(valid[None], jnp.exp(s - lse), 0.0)
    dp = lax.dot_general(
        do, v, (((2,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # (rep, bq, bk)
    return p, p * (dp - delta)


def _flash_gqa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                             delta_ref, dq_ref, acc_ref, *,
                             causal: bool, scale: float, tk: int,
                             bq: int, bk: int, n_k: int):
    i = pl.program_id(2)          # q block index
    j = pl.program_id(3)          # k block index (innermost)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    compute = (j * bk <= i * bq + (bq - 1)) if causal else True

    @pl.when(compute)
    def _():
        _, ds = _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                          delta_ref, i, j, causal=causal, scale=scale,
                          tk=tk, bq=bq, bk=bk)
        k = k_ref[0, 0]
        acc_ref[...] += lax.dot_general(
            ds.astype(k.dtype), k, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (rep, bq, D)

    @pl.when(j == n_k - 1)
    def _():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _flash_gqa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                              delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                              *, causal: bool, scale: float, tk: int,
                              bq: int, bk: int, n_q: int):
    j = pl.program_id(2)          # k block index
    i = pl.program_id(3)          # q block index (innermost)

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # causal: a q block strictly above the k block contributes nothing
    compute = (i * bq + (bq - 1) >= j * bk) if causal else True

    @pl.when(compute)
    def _():
        p, ds = _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                          delta_ref, i, j, causal=causal, scale=scale,
                          tk=tk, bq=bq, bk=bk)
        # dv += Σ_rep p^T do ; dk += Σ_rep ds^T q.  Mosaic's matmul takes
        # ONE contracting dimension, so (rep, bq) merge into rows first (a
        # layout no-op: bq is a sublane multiple) — the GQA group sums
        # still fall out of the contraction, nothing rep-sized is
        # materialized
        rows = p.shape[0] * p.shape[1]
        q = q_ref[0, 0].reshape(rows, -1)
        do = do_ref[0, 0].reshape(rows, -1)
        dv_acc[...] += lax.dot_general(
            p.reshape(rows, bk).astype(do.dtype), do,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, Dv)
        dk_acc[...] += lax.dot_general(
            ds.reshape(rows, bk).astype(q.dtype), q,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, D)

    @pl.when(i == n_q - 1)
    def _():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _row_layout(x, hkv, rep, tq_p):
    """(B, Tq, H) -> padded (B, H_kv, rep, Tq_p)."""
    b, tq, _ = x.shape
    return jnp.pad(x.reshape(b, tq, hkv, rep).transpose(0, 2, 3, 1),
                   ((0, 0), (0, 0), (0, 0), (0, tq_p - tq)))


@functools.partial(jax.jit, static_argnums=(6, 7))
def _flash_gqa_bwd_call(q, k, v, out, lse, do, causal: bool,
                        interpret: bool):
    """Pallas flash backward: (dq, dk, dv) in the input shapes/dtypes.
    q/k/dq/dk are laid out and padded at D's width, v/do/dv at Dv's."""
    b, tq, h, d = q.shape
    tk, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    rep = h // hkv
    d_p, dv_p = _pad128(d), _pad128(dv)
    scale = 1.0 / float(d) ** 0.5
    bq, bk = _bwd_blocks(rep, tq, tk)
    tq_p, tk_p = -(-tq // bq) * bq, -(-tk // bk) * bk
    n_q, n_k = tq_p // bq, tk_p // bk
    qt = _q_layout(q, hkv, rep, tq_p, d_p)
    kt = _kv_layout(k, tk_p, d_p)
    vt = _kv_layout(v, tk_p, dv_p)
    dot = _q_layout(do, hkv, rep, tq_p, dv_p)
    # delta_i = Σ_d dO_id · O_id (the flash-backward row constant).  Pad
    # rows (the forward's block length need not be this one's: lse is cut
    # to Tq and padded anew) have q = dO = 0 and lse = delta = 0: p = 1
    # there, ds = 0, and they add nothing to dk or dv
    delta = _row_layout(
        (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1),
        hkv, rep, tq_p)
    lse = jnp.pad(lse[..., :tq], ((0, 0),) * 3 + ((0, tq_p - tq),))

    def specs(qi, ki):
        """Block specs on a grid (b, kv head, x, y); `qi`/`ki` give the q
        and the k block's index from (x, y)."""
        def wide(width):
            return pl.BlockSpec(
                (1, 1, rep, bq, width),
                lambda bi, g, x, y: (bi, g, 0, qi(x, y), 0),
                memory_space=pltpu.VMEM)

        def kv(width):
            return pl.BlockSpec(
                (1, 1, bk, width),
                lambda bi, g, x, y: (bi, g, ki(x, y), 0),
                memory_space=pltpu.VMEM)

        row = pl.BlockSpec((1, 1, rep, bq),
                           lambda bi, g, x, y: (bi, g, 0, qi(x, y)),
                           memory_space=pltpu.VMEM)
        return wide(d_p), wide(dv_p), kv(d_p), kv(dv_p), row

    params = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)
    qd, qdv, kd, kdv, row = specs(
        lambda i, j: i,
        (lambda i, j: _dq_k_block(i, j, bq, bk)) if causal
        else (lambda i, j: j))
    call = pl.pallas_call(
        functools.partial(_flash_gqa_bwd_dq_kernel, causal=causal,
                          scale=scale, tk=tk, bq=bq, bk=bk, n_k=n_k),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, tq_p, d_p), q.dtype),
        grid=(b, hkv, n_q, n_k),
        in_specs=[qd, kd, kdv, qdv, row, row],
        out_specs=pl.BlockSpec((1, 1, rep, bq, d_p),
                               lambda bi, g, i, j: (bi, g, 0, i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((rep, bq, d_p), jnp.float32)],
        compiler_params=params, interpret=interpret,
        name=scopes.kernel_name(scopes.KERNEL_FLASH_GQA_BWD_DQ),
    )
    with jax.named_scope(scopes.KERNEL_FLASH_GQA_BWD_DQ):
        dq = call(qt, kt, vt, dot, lse, delta)

    # k-major grid: the q-block index is innermost for the accumulators
    qd, qdv, kd, kdv, row = specs(
        (lambda j, i: _dkv_q_block(j, i, bq, bk, n_q)) if causal
        else (lambda j, i: i),
        lambda j, i: j)
    call = pl.pallas_call(
        functools.partial(_flash_gqa_bwd_dkv_kernel, causal=causal,
                          scale=scale, tk=tk, bq=bq, bk=bk, n_q=n_q),
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, tk_p, d_p), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, tk_p, dv_p), v.dtype),
        ),
        grid=(b, hkv, n_k, n_q),
        in_specs=[qd, kd, kdv, qdv, row, row],
        out_specs=(
            pl.BlockSpec((1, 1, bk, d_p),
                         lambda bi, g, j, i: (bi, g, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, dv_p),
                         lambda bi, g, j, i: (bi, g, j, 0),
                         memory_space=pltpu.VMEM)),
        scratch_shapes=[pltpu.VMEM((bk, d_p), jnp.float32),
                        pltpu.VMEM((bk, dv_p), jnp.float32)],
        compiler_params=params, interpret=interpret,
        name=scopes.kernel_name(scopes.KERNEL_FLASH_GQA_BWD_DKV),
    )
    with jax.named_scope(scopes.KERNEL_FLASH_GQA_BWD_DKV):
        dk, dvv = call(qt, kt, vt, dot, lse, delta)

    dq = dq[:, :, :, :tq, :d].transpose(0, 3, 1, 2, 4).reshape(
        b, tq, h, d)
    dk = dk[:, :, :tk, :d].transpose(0, 2, 1, 3)
    dvv = dvv[:, :, :tk, :dv].transpose(0, 2, 1, 3)
    return dq, dk, dvv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_gqa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = True) -> jnp.ndarray:
    """Flash attention with GQA-native unexpanded K/V, on the MXU.

    q: (B, Tq, H, D); k: (B, Tk, H_kv, D); v: (B, Tk, H_kv, Dv) with
    H_kv | H (kv head g serves q heads [g·rep, (g+1)·rep), the
    `grouped_query_attention` convention).  rep == 1 is plain MHA.  Dv
    may differ from D (latent attention: 192-wide q/k, 128-wide v); the
    softmax scale is 1/sqrt(D) and each width is padded on its own.
    Tq/Tk/D/Dv need no alignment — padding is handled internally
    (masked, never averaged in).  Returns (B, Tq, H, Dv) in q.dtype;
    fp32 softmax.

    Matches `_chunked_attention` / `grouped_query_attention` to fp32
    round-off (different contraction order — not bitwise).  Compiled by
    Mosaic on TPU, interpreted on the CPU test backend
    (`ops.backend.interpret_mode`); `tools/pallas_check.py` checks the
    compiled kernels on the chip.

    The gradient is the flash-backward recipe as two Pallas kernels (dq
    with K innermost; fused dk/dv with Q innermost, the GQA group-sums
    folded into the (rep, bq) contractions) against the forward's saved
    LSE — O(1) extra memory, forward and backward on the MXU.  It is
    tested against `jax.grad` of `_chunked_attention` (the XLA gradient,
    which `attn_impl="chunked"` still runs) and the exact XLA gradient.
    """
    _gqa_rep(q, k)  # H_kv | H (shared contract, attention.py)
    out, _ = _flash_gqa_fwd_call(q, k, v, causal, interpret_mode())
    return out


# The names of the forward kernel's two results among the gradient's
# residuals.  Outside a `jax.checkpoint` a name is an identity; inside
# one, a policy that saves them keeps them from the forward pass
FLASH_OUT = "flash_gqa.out"
FLASH_LSE = "flash_gqa.lse"

# The policy of a recomputed block that calls `flash_gqa`: keep the
# kernel's output (bf16, the size of q) and its log-sum-exp (a float32 per
# query row), recompute everything else.  Without it the backward pass
# re-runs the forward kernel and its layout passes for every block, which
# costs the kernel's time again (a Moonlight layer on a v5e: 7.5 ms for
# 65 MiB kept at 2 x 8,192 tokens; PERF.md section 6).  q, k and v are
# not named: they are the projections' outputs, three to four times what
# is kept, and a block recomputes them with the rest.  A block without the names
# saves nothing under it, as under no policy
KEEP_FLASH_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    FLASH_OUT, FLASH_LSE)


def _fwd(q, k, v, causal):
    # custom_vjp bypasses the primal under jax.grad: the head ratio is
    # checked here too
    _gqa_rep(q, k)
    out, lse = _flash_gqa_fwd_call(q, k, v, causal, interpret_mode())
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, out, lse)


def _bwd(causal, res, g):
    return _flash_gqa_bwd_call(*res, g, causal, interpret_mode())


flash_gqa.defvjp(_fwd, _bwd)
