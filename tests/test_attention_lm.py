"""Ring attention + transformer LM + dp/sp/tp train step tests on the
8-virtual-device CPU mesh.

Long-context / multi-axis parallelism is new capability beyond the
reference (SURVEY.md §5: absent there); correctness oracle is agreement
between the sharded and single-device executions of the same math.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from cpd_tpu.compat import shard_map
from cpd_tpu.models.transformer import (TransformerLM, lm_param_specs,
                                        transformer_lm)
from cpd_tpu.ops.attention import local_attention, ring_attention
from cpd_tpu.parallel.mesh import make_mesh


def _rand_qkv(rng, b=2, t=32, h=4, d=8):
    q = rng.randn(b, t, h, d).astype(np.float32)
    k = rng.randn(b, t, h, d).astype(np.float32)
    v = rng.randn(b, t, h, d).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def test_local_attention_matches_naive():
    rng = np.random.RandomState(0)
    q, k, v = _rand_qkv(rng)
    out = local_attention(q, k, v, causal=True)
    # naive reference
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = np.einsum("bqhd,bkhd->bhqk", q, k) * scale
    tq = q.shape[1]
    mask = np.tril(np.ones((tq, tq), bool))
    logits = np.where(mask[None, None], logits, -1e30)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", probs, v)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_local(causal):
    """Ring attention over sp=8 equals single-device attention on the full
    sequence."""
    rng = np.random.RandomState(1)
    q, k, v = _rand_qkv(rng, b=2, t=64, h=2, d=16)
    full = local_attention(q, k, v, causal=causal)

    mesh = make_mesh(sp=8, dp=1)

    def body(ql, kl, vl):
        return ring_attention(ql, kl, vl, "sp", causal=causal)

    sharded = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_grads_match():
    rng = np.random.RandomState(2)
    q, k, v = _rand_qkv(rng, b=1, t=32, h=2, d=8)
    mesh = make_mesh(sp=8, dp=1)

    def loss_full(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=True) ** 2)

    def loss_ring(q, k, v):
        def body(ql, kl, vl):
            o = ring_attention(ql, kl, vl, "sp", causal=True)
            return lax.psum(jnp.sum(o ** 2), "sp")
        return shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "sp"),) * 3, out_specs=P(),
            check_vma=False)(q, k, v)

    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_full, g_ring):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_local(causal):
    """All-to-all sequence parallelism over sp=8 equals single-device
    attention (heads 8 % sp 8 == 0)."""
    from cpd_tpu.ops.attention import ulysses_attention

    rng = np.random.RandomState(11)
    q, k, v = _rand_qkv(rng, b=2, t=64, h=8, d=8)
    full = local_attention(q, k, v, causal=causal)

    mesh = make_mesh(sp=8, dp=1)

    def body(ql, kl, vl):
        return ulysses_attention(ql, kl, vl, "sp", causal=causal)

    sharded = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_attention_grads_match():
    from cpd_tpu.ops.attention import ulysses_attention

    rng = np.random.RandomState(12)
    q, k, v = _rand_qkv(rng, b=1, t=32, h=8, d=8)
    mesh = make_mesh(sp=8, dp=1)

    def loss_full(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=True) ** 2)

    def loss_uly(q, k, v):
        def body(ql, kl, vl):
            o = ulysses_attention(ql, kl, vl, "sp", causal=True)
            return lax.psum(jnp.sum(o ** 2), "sp")
        return shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "sp"),) * 3, out_specs=P(),
            check_vma=False)(q, k, v)

    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_uly = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_full, g_uly):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


def _rand_gqa(rng, b=2, t=64, h=8, hkv=2, d=8):
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, hkv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, hkv, d).astype(np.float32))
    return q, k, v


def test_ring_attention_gqa_unexpanded_parity():
    """GQA K/V ride the ring UNEXPANDED (round 4): the grouped per-step
    contraction computes the same dot products as the expanded ring —
    last-ulp agreement (XLA's batched-matmul layout differs, so not
    bitwise; measured max |diff| 5e-7) — and matches the full-sequence
    grouped oracle."""
    from cpd_tpu.ops.attention import grouped_query_attention

    rng = np.random.RandomState(21)
    q, k, v = _rand_gqa(rng, h=8, hkv=2)
    rep = q.shape[2] // k.shape[2]
    full = grouped_query_attention(q, k, v, causal=True)

    mesh = make_mesh(sp=8, dp=1)

    def run(kk, vv):
        def body(ql, kl, vl):
            return ring_attention(ql, kl, vl, "sp", causal=True)
        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False))(q, kk, vv)

    unexp = run(k, v)
    np.testing.assert_allclose(np.asarray(unexp), np.asarray(full),
                               rtol=2e-5, atol=2e-5)
    exp = run(jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2))
    np.testing.assert_allclose(np.asarray(unexp), np.asarray(exp),
                               rtol=1e-5, atol=1e-6)


def test_ring_attention_gqa_grads_match():
    """Backward through the grouped ring (reshapes + ppermute transpose)
    equals the single-device grouped oracle's gradients."""
    from cpd_tpu.ops.attention import grouped_query_attention

    rng = np.random.RandomState(22)
    q, k, v = _rand_gqa(rng, b=1, t=32, h=4, hkv=2)
    mesh = make_mesh(sp=8, dp=1)

    def loss_full(q, k, v):
        return jnp.sum(grouped_query_attention(q, k, v, causal=True) ** 2)

    def loss_ring(q, k, v):
        def body(ql, kl, vl):
            o = ring_attention(ql, kl, vl, "sp", causal=True)
            return lax.psum(jnp.sum(o ** 2), "sp")
        return shard_map(
            body, mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(),
            check_vma=False)(q, k, v)

    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_full, g_ring):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("hkv,sp", [(4, 4), (2, 4)])
def test_ulysses_attention_gqa(hkv, sp):
    """Ulysses with GQA: hkv=4 % sp=4 == 0 goes through the all_to_all
    UNEXPANDED; hkv=2, sp=4 triggers the minimal internal expansion
    (e=2, not the full rep=4).  Both match the grouped oracle and the
    legacy fully-expanded ulysses (last-ulp: grouped-einsum layout)."""
    from cpd_tpu.ops.attention import (grouped_query_attention,
                                       ulysses_attention)

    rng = np.random.RandomState(23)
    q, k, v = _rand_gqa(rng, h=8, hkv=hkv, t=32)
    rep = q.shape[2] // hkv
    full = grouped_query_attention(q, k, v, causal=True)

    mesh = make_mesh(sp=sp, dp=1, devices=jax.devices()[:sp])

    def run(kk, vv):
        def body(ql, kl, vl):
            return ulysses_attention(ql, kl, vl, "sp", causal=True)
        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False))(q, kk, vv)

    unexp = run(k, v)
    np.testing.assert_allclose(np.asarray(unexp), np.asarray(full),
                               rtol=2e-5, atol=2e-5)
    exp = run(jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2))
    np.testing.assert_allclose(np.asarray(unexp), np.asarray(exp),
                               rtol=1e-5, atol=1e-6)


class TestChunkedAttention:
    """impl='chunked': the pure-XLA online-softmax K/V-block scan must
    match the one-shot softmax to fp32 round-off — uniform and GQA
    heads, causal and not, Tk not a multiple of the block (pad+mask
    path), gradients, offsets, and the ulysses composition."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("hkv", [4, 2])
    def test_matches_oracle(self, causal, hkv):
        from cpd_tpu.ops.attention import (_chunked_attention,
                                           grouped_query_attention)

        rng = np.random.RandomState(31)
        q, k, v = _rand_gqa(rng, b=2, t=40, h=4, hkv=hkv, d=8)
        want = grouped_query_attention(q, k, v, causal=causal)
        got = _chunked_attention(q, k, v, causal, 0, 0, block=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        # public routes (default block > T: single padded block)
        via_grouped = grouped_query_attention(q, k, v, causal=causal,
                                              impl="chunked")
        np.testing.assert_allclose(np.asarray(via_grouped),
                                   np.asarray(want), rtol=2e-5, atol=2e-5)

    def test_offsets_match_xla_path(self):
        from cpd_tpu.ops.attention import _chunked_attention, local_attention

        rng = np.random.RandomState(32)
        q, k, v = _rand_qkv(rng, b=1, t=24, h=2, d=8)
        want = local_attention(q, k, v, causal=True, q_offset=24,
                               k_offset=8)
        got = _chunked_attention(q, k, v, True, 24, 8, block=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_fully_masked_rows_zero_in_every_impl(self):
        """A causal shard whose keys are ALL in the future (q_offset +
        Tq <= k_offset) has no attendable key; every impl returns 0 —
        the flash convention, pinned impl-interchangeable since round 5
        (ADVICE r4: chunked previously averaged PAD keys into such
        rows, the one-shot softmax fell back to a uniform average)."""
        from cpd_tpu.ops.attention import _chunked_attention, local_attention

        rng = np.random.RandomState(35)
        q, k, v = _rand_qkv(rng, b=1, t=24, h=2, d=8)
        # all 24 query rows sit before key offset 64: fully masked
        one_shot = local_attention(q, k, v, causal=True, q_offset=0,
                                   k_offset=64)
        chunked = _chunked_attention(q, k, v, True, 0, 64, block=16)
        assert np.all(np.asarray(one_shot) == 0.0)
        assert np.all(np.asarray(chunked) == 0.0)
        # sanity: a PARTIALLY masked call still matches the oracle
        part = _chunked_attention(q, k, v, True, 12, 8, block=16)
        want = local_attention(q, k, v, causal=True, q_offset=12,
                               k_offset=8)
        np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_grads_match(self):
        from cpd_tpu.ops.attention import (_chunked_attention,
                                           local_attention)

        rng = np.random.RandomState(33)
        q, k, v = _rand_qkv(rng, b=1, t=32, h=2, d=8)

        g_ref = jax.grad(lambda a, b_, c: jnp.sum(
            local_attention(a, b_, c, causal=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        g_chk = jax.grad(lambda a, b_, c: jnp.sum(
            _chunked_attention(a, b_, c, True, 0, 0, block=8) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g_ref, g_chk):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=5e-5, atol=5e-5)

    @pytest.mark.parametrize("hkv", [2])  # hkv=1 (MQA) rides the slow
    # long-context smoke (test_long_context_ring_chunked_smoke)
    def test_ring_chunked_inner_fold(self, hkv):
        """ring impl='chunked' with block | T_local engages the inner
        sub-block scan and still matches the one-shot grouped oracle —
        and its grads match the plain ring's."""
        from cpd_tpu.ops.attention import (grouped_query_attention,
                                           ring_attention)

        rng = np.random.RandomState(35)
        q, k, v = _rand_gqa(rng, b=1, t=64, h=2, hkv=hkv, d=8)
        full = grouped_query_attention(q, k, v, causal=True)
        mesh = make_mesh(sp=4, dp=1, devices=jax.devices()[:4])
        # T_local = 16; block=4 -> 4 inner folds per ring step
        def body(ql, kl, vl):
            return ring_attention(ql, kl, vl, "sp", causal=True,
                                  impl="chunked", block=4)
        got = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False))(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                                   rtol=2e-5, atol=2e-5)

        def loss(impl, block):
            def body(ql, kl, vl):
                o = ring_attention(ql, kl, vl, "sp", causal=True,
                                   impl=impl, block=block)
                return lax.psum(jnp.sum(o ** 2), "sp")
            return shard_map(
                body, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                out_specs=P(), check_vma=False)
        g_ref = jax.grad(lambda a, b_, c: loss("xla", 512)(a, b_, c),
                         argnums=(0, 1, 2))(q, k, v)
        g_chk = jax.grad(lambda a, b_, c: loss("chunked", 4)(a, b_, c),
                         argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g_ref, g_chk):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=5e-5, atol=5e-5)

    def test_ring_chunked_divisor_and_degenerate(self):
        """block ∤ T_local picks the largest divisor (memory bound kept,
        never a silent whole-block fold); a degenerate split (prime
        T_local) raises."""
        from cpd_tpu.ops.attention import local_attention, ring_attention

        rng = np.random.RandomState(36)
        # T=96 over sp=2 -> T_local=48; block=32 ∤ 48 -> divisor 24
        q, k, v = _rand_qkv(rng, b=1, t=96, h=2, d=8)
        full = local_attention(q, k, v, causal=True)
        mesh = make_mesh(sp=2, dp=1, devices=jax.devices()[:2])

        def run(block, t_slice=96):
            def body(ql, kl, vl):
                return ring_attention(ql, kl, vl, "sp", causal=True,
                                      impl="chunked", block=block)
            return jax.jit(shard_map(
                body, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                out_specs=P(None, "sp"), check_vma=False))(
                    q[:, :t_slice], k[:, :t_slice], v[:, :t_slice])

        got = run(32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                                   rtol=2e-5, atol=2e-5)
        # T=94 -> T_local=47 (prime): degenerate, loud
        import pytest as _pytest
        with _pytest.raises(ValueError, match="degenerate"):
            run(32, t_slice=94)

    def test_ulysses_chunked_gqa(self):
        from cpd_tpu.ops.attention import (grouped_query_attention,
                                           ulysses_attention)

        rng = np.random.RandomState(34)
        q, k, v = _rand_gqa(rng, b=2, t=32, h=8, hkv=4, d=8)
        want = grouped_query_attention(q, k, v, causal=True)
        mesh = make_mesh(sp=4, dp=1, devices=jax.devices()[:4])

        def body(ql, kl, vl):
            return ulysses_attention(ql, kl, vl, "sp", causal=True,
                                     impl="chunked")

        got = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False))(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_ulysses_flash_gqa_native_unexpanded(monkeypatch):
    """With impl='flash' and GQA, ulysses hands the UNEXPANDED K/V chunk
    to the GQA-native Pallas kernel (ops/flash_gqa.py, round 5) — no
    rep× re-materialization on either side of the all_to_all.  The
    kernel runs for real here (interpret mode off-TPU); the spy pins the
    ROUTING: grouped heads reach the kernel unexpanded."""
    import sys

    import cpd_tpu.ops.flash_gqa  # noqa: F401 — ensure module is loaded
    # the package re-exports the function under the submodule's name, so
    # reach the MODULE through sys.modules for patching
    fg_mod = sys.modules["cpd_tpu.ops.flash_gqa"]
    from cpd_tpu.ops.attention import (grouped_query_attention,
                                       ulysses_attention)

    calls = {}
    real = fg_mod.flash_gqa

    def spy(q, k, v, causal=True):
        calls["heads"] = (q.shape[2], k.shape[2])
        return real(q, k, v, causal)

    monkeypatch.setattr(fg_mod, "flash_gqa", spy)
    rng = np.random.RandomState(24)
    q, k, v = _rand_gqa(rng, h=8, hkv=4, t=32)
    full = grouped_query_attention(q, k, v, causal=True)
    mesh = make_mesh(sp=4, dp=1, devices=jax.devices()[:4])

    def body(ql, kl, vl):
        return ulysses_attention(ql, kl, vl, "sp", causal=True,
                                 impl="flash")

    out = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=2e-5, atol=2e-5)
    assert calls["heads"] == (2, 1)  # grouped heads, K/V unexpanded


@pytest.mark.slow
def test_long_context_ring_chunked_smoke():
    """Long-context path at depth: T=2048 over sp=8 ring with the
    chunked inner fold (T_local=256, block=128 -> 2 inner folds x 8
    ring steps).  Forward parity vs the plain ring, and one LM train
    step on the dp1 x sp8 mesh runs finite and seed-deterministic."""
    from cpd_tpu.models import transformer_lm
    from cpd_tpu.ops.attention import ring_attention
    from cpd_tpu.train import (create_train_state, make_lm_train_step,
                               make_optimizer)

    rng = np.random.RandomState(41)
    q = jnp.asarray(rng.randn(1, 2048, 2, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2048, 1, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2048, 1, 8).astype(np.float32))
    mesh = make_mesh(sp=8, dp=1)

    def run(impl, block):
        def body(ql, kl, vl):
            return ring_attention(ql, kl, vl, "sp", causal=True,
                                  impl=impl, block=block)
        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False))(q, k, v)

    plain = run("xla", 512)
    chunked = run("chunked", 128)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(plain),
                               rtol=2e-5, atol=2e-5)

    # one real train step at T=2048 through the model's chunked-ring path
    toks = jnp.asarray(rng.randint(0, 64, (8, 2048)).astype(np.int32))
    tgts = jnp.asarray(np.roll(np.asarray(toks), -1, axis=1))
    model = transformer_lm(vocab_size=64, d_model=32, n_layers=1,
                           n_heads=2, n_kv_heads=1, d_ff=64,
                           sp_axis="sp", attn_impl="chunked")
    init_model = transformer_lm(vocab_size=64, d_model=32, n_layers=1,
                                n_heads=2, n_kv_heads=1, d_ff=64)
    tx = make_optimizer("sgd", lambda s: jnp.float32(0.05), momentum=0.9)
    state = create_train_state(init_model, tx, toks[:1],
                               jax.random.PRNGKey(0))
    step = make_lm_train_step(model, tx, mesh, donate=False)
    s1, m1 = step(state, toks, tgts)
    assert np.isfinite(float(m1["loss"]))
    s2, m2 = step(state, toks, tgts)
    assert float(m1["loss"]) == float(m2["loss"])


@pytest.mark.slow
def test_lm_dropout():
    """Dropout: eval is identity (same logits as the rate-0 model on the
    same params), the train step is rng-deterministic, and dropping
    actually changes the training loss."""
    from cpd_tpu.train import (create_train_state, make_lm_train_step,
                               make_optimizer)

    rng = np.random.RandomState(61)
    toks = jnp.asarray(rng.randint(0, 64, (8, 16)).astype(np.int32))
    tgts = jnp.roll(toks, -1, axis=1)

    plain = _tiny_lm()
    dropped = _tiny_lm(dropout_rate=0.5)
    params = plain.init(jax.random.PRNGKey(0), toks)["params"]
    # no new params; eval-mode forward identical
    assert (jax.tree_util.tree_structure(params) == jax.tree_util
            .tree_structure(dropped.init(jax.random.PRNGKey(0),
                                         toks)["params"]))
    np.testing.assert_array_equal(
        np.asarray(plain.apply({"params": params}, toks, train=False)),
        np.asarray(dropped.apply({"params": params}, toks, train=False)))

    mesh = make_mesh(dp=2, sp=2, tp=2)
    tx = make_optimizer("sgd", lambda s: 0.0)
    sh = _tiny_lm(dropout_rate=0.5, tp_axis="tp", sp_axis="sp", tp_size=2)
    state = create_train_state(_tiny_lm(dropout_rate=0.5), tx, toks[:1],
                               jax.random.PRNGKey(0))
    step = make_lm_train_step(sh, tx, mesh, donate=False)
    _, m1 = step(state, toks, tgts)
    _, m2 = step(state, toks, tgts)
    assert np.isfinite(float(m1["loss"]))
    # rng deterministic in (seed, step): identical repeat
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]))
    # and different from the undropped loss (single-device reference —
    # compiling a third dp x sp x tp step just for this comparison cost
    # ~10s of suite budget; the sharded==single-device loss parity is
    # test_lm_train_step_dp_sp_tp's job)
    import optax

    logits0 = plain.apply({"params": state.params}, toks)
    loss0 = optax.softmax_cross_entropy_with_integer_labels(
        logits0, tgts).mean()
    assert abs(float(m1["loss"]) - float(loss0)) > 1e-4

    # composes with scan_layers (the dropout rng must be lifted through
    # nn.scan's split_rngs or apply raises InvalidRngError)
    scan_model = _tiny_lm(dropout_rate=0.5, scan_layers=True)
    scan_state = create_train_state(scan_model, tx, toks[:1],
                                    jax.random.PRNGKey(0))
    mesh_dp = make_mesh(dp=8)
    _, ms = make_lm_train_step(scan_model, tx, mesh_dp, donate=False)(
        scan_state, toks, tgts)
    assert np.isfinite(float(ms["loss"]))

    # invalid rates fail loudly instead of silently zeroing branches
    bad = _tiny_lm(dropout_rate=1.0)
    with pytest.raises(ValueError, match="dropout_rate"):
        bad.init(jax.random.PRNGKey(0), toks)


@pytest.mark.slow  # feature-level LM compile; core LM step stays fast via test_lm_train_step_dp_sp_tp
def test_lm_label_smoothing():
    """Smoothed loss matches the closed form at step level: ls=0 equals
    plain CE; ls>0 loss is finite and differs; invalid ls raises."""
    from cpd_tpu.train import (create_train_state, make_lm_train_step,
                               make_optimizer)

    mesh = make_mesh(dp=len(jax.devices()))
    tx = make_optimizer("sgd", lambda s: 0.0)   # lr 0: loss is pure fwd
    model = _tiny_lm()
    rng = np.random.RandomState(51)
    toks = jnp.asarray(rng.randint(0, 64, (8, 16)).astype(np.int32))
    tgts = jnp.roll(toks, -1, axis=1)
    state = create_train_state(model, tx, toks[:1], jax.random.PRNGKey(0))

    def loss_at(ls):
        step = make_lm_train_step(model, tx, mesh, donate=False,
                                  label_smoothing=ls)
        _, m = step(state, toks, tgts)
        return float(m["loss"])

    plain = loss_at(0.0)
    import optax
    logits = model.apply({"params": jax.device_get(state.params)}, toks)
    want = float(optax.softmax_cross_entropy_with_integer_labels(
        logits, tgts).mean())
    np.testing.assert_allclose(plain, want, rtol=1e-5)

    ls = 0.1
    smoothed = loss_at(ls)
    soft = (jax.nn.one_hot(tgts, 64) * (1 - ls) + ls / 64)
    want_s = float(optax.softmax_cross_entropy(logits, soft).mean())
    np.testing.assert_allclose(smoothed, want_s, rtol=1e-5)

    with pytest.raises(ValueError, match="label_smoothing"):
        make_lm_train_step(model, tx, mesh, label_smoothing=1.5)


def test_lm_remat_grads_match():
    """jax.checkpoint per block changes memory, not math: params and
    gradients identical with and without remat (single device AND the
    sharded dp/sp/tp step path via param-name equality)."""
    import optax

    rng = np.random.RandomState(21)
    toks = jnp.asarray(rng.randint(0, 64, (2, 16)).astype(np.int32))
    tgts = jnp.roll(toks, -1, axis=1)

    plain = _tiny_lm()
    remat = _tiny_lm(remat=True)
    params = plain.init(jax.random.PRNGKey(0), toks)["params"]
    # identical param trees (remat is transparent to naming/shapes)
    r_params = remat.init(jax.random.PRNGKey(0), toks)["params"]
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(r_params))

    def loss(m, p):
        logits = m.apply({"params": p}, toks)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgts).mean()

    g_plain = jax.grad(lambda p: loss(plain, p))(params)
    g_remat = jax.grad(lambda p: loss(remat, p))(params)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(g_plain)[0],
            jax.tree_util.tree_flatten_with_path(g_remat)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7, err_msg=str(path))


@pytest.mark.slow  # remat value/grad parity stays fast via test_lm_remat_grads_match
def test_lm_remat_sharded_step_runs():
    """remat composes with the full quantized dp x sp x tp train step
    (ring attention's ppermute recomputes inside jax.checkpoint)."""
    from cpd_tpu.train import (create_train_state, make_lm_train_step,
                               make_optimizer)

    mesh = make_mesh(dp=2, sp=2, tp=2)
    model = _tiny_lm(tp_axis="tp", sp_axis="sp", tp_size=2, remat=True)
    tx = make_optimizer("sgd", lambda s: 0.2, momentum=0.9)
    rng = np.random.RandomState(22)
    toks = jnp.asarray(rng.randint(0, 64, (4, 32)).astype(np.int32))
    tgts = jnp.roll(toks, -1, axis=1)
    state = create_train_state(_tiny_lm(), tx, toks[:1],
                               jax.random.PRNGKey(2))
    step = make_lm_train_step(model, tx, mesh, use_aps=True, grad_exp=5,
                              grad_man=2, donate=False)
    state, metrics = step(state, toks, tgts)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("causal,q_off", [(True, 0), (True, 3),
                                          (False, 0)])
def test_grouped_query_attention_matches_expanded(causal, q_off):
    """The grouped kernel == local_attention over explicitly repeated
    K/V (the expansion it exists to avoid materializing)."""
    from cpd_tpu.ops.attention import grouped_query_attention

    rng = np.random.RandomState(40)
    b, tq, tk, hkv, rep, d = 2, 5, 8, 2, 3, 8
    q = jnp.asarray(rng.randn(b, tq, hkv * rep, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, tk, hkv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, tk, hkv, d).astype(np.float32))

    got = grouped_query_attention(q, k, v, causal=causal, q_offset=q_off)
    ke = jnp.repeat(k, rep, axis=2)
    ve = jnp.repeat(v, rep, axis=2)
    want = local_attention(q, ke, ve, causal=causal, q_offset=q_off)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


def test_lm_gqa_sharded_forward_matches_single():
    """GQA (2 kv heads serving 4 q heads) under dp2 x sp2 x tp2 equals
    the single-device forward — the kv-group <-> tp-slice consistency
    oracle."""
    rng = np.random.RandomState(41)
    toks = jnp.asarray(rng.randint(0, 64, (4, 32)).astype(np.int32))

    ref_model = _tiny_lm(n_kv_heads=2)
    params = ref_model.init(jax.random.PRNGKey(1), toks[:1])["params"]
    want = ref_model.apply({"params": params}, toks)

    mesh = make_mesh(dp=2, sp=2, tp=2)
    sh_model = _tiny_lm(n_kv_heads=2, tp_axis="tp", sp_axis="sp",
                        tp_size=2)
    specs = lm_param_specs(params, "tp")
    out = jax.jit(shard_map(
        lambda p, t: sh_model.apply({"params": p}, t),
        mesh=mesh, in_specs=(specs, P("dp", "sp")),
        out_specs=P("dp", "sp"), check_vma=False))(params, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_lm_gqa_decode_matches_full_forward():
    """GQA decode caches the UNEXPANDED kv heads; prefill logits must
    still equal the full causal forward."""
    model = _tiny_lm(n_kv_heads=2)
    toks = jnp.asarray(np.random.RandomState(42).randint(
        0, 64, (2, 10)).astype(np.int32))
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    full = model.apply({"params": params}, toks)

    dec = model.clone(decode=True)
    cache = dec.init(jax.random.PRNGKey(1), jnp.zeros((2, 16), jnp.int32),
                     train=False)["cache"]
    # the cache holds 2 kv heads, not 4 — the GQA memory win
    assert cache["block0"]["cached_k"].shape[-2] == 2
    pre, _ = dec.apply({"params": params, "cache": cache}, toks,
                       train=False, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full),
                               rtol=5e-5, atol=5e-5)


def test_lm_scan_layers_matches_unrolled():
    """nn.scan'd block stack == the Python-loop stack: stacking the loop
    model's per-layer params along a leading axis reproduces the scanned
    model's logits exactly."""
    rng = np.random.RandomState(31)
    toks = jnp.asarray(rng.randint(0, 64, (2, 16)).astype(np.int32))

    loop = _tiny_lm()
    scan = _tiny_lm(scan_layers=True)
    lp = loop.init(jax.random.PRNGKey(0), toks)["params"]

    n_layers = 2
    blocks = [lp[f"block{i}"] for i in range(n_layers)]
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls, axis=0), *blocks)
    sp = {"blocks": stacked}
    sp.update({k: v for k, v in lp.items()
               if not k.startswith("block")})
    # structure agreement with a fresh scanned init
    si = scan.init(jax.random.PRNGKey(0), toks)["params"]
    assert (jax.tree_util.tree_structure(si)
            == jax.tree_util.tree_structure(sp))

    want = loop.apply({"params": lp}, toks)
    got = scan.apply({"params": sp}, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_lm_scan_layers_sharded_step_runs():
    """scan_layers composes with remat and the quantized dp x sp x tp
    train step (rank-aware lm_param_specs shard the stacked kernels)."""
    from cpd_tpu.train import (create_train_state, make_lm_train_step,
                               make_optimizer)

    mesh = make_mesh(dp=2, sp=2, tp=2)
    model = _tiny_lm(tp_axis="tp", sp_axis="sp", tp_size=2,
                     scan_layers=True, remat=True)
    tx = make_optimizer("sgd", lambda s: 0.2, momentum=0.9)
    rng = np.random.RandomState(32)
    toks = jnp.asarray(rng.randint(0, 64, (4, 32)).astype(np.int32))
    tgts = jnp.roll(toks, -1, axis=1)
    state = create_train_state(_tiny_lm(scan_layers=True), tx, toks[:1],
                               jax.random.PRNGKey(2))
    step = make_lm_train_step(model, tx, mesh, use_aps=True, grad_exp=5,
                              grad_man=2, donate=False)
    state, metrics = step(state, toks, tgts)
    assert np.isfinite(float(metrics["loss"]))


def test_lm_scan_layers_decode_raises():
    model = _tiny_lm(scan_layers=True, decode=True)
    with pytest.raises(ValueError, match="scan_layers"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_lm_unknown_sp_mode_raises():
    model = _tiny_lm(sp_axis="sp", sp_mode="ulysess")  # typo must not
    toks = jnp.zeros((1, 8), jnp.int32)                # silently ring
    mesh = make_mesh(sp=8, dp=1)
    with pytest.raises(ValueError, match="sp_mode"):
        shard_map(
            lambda t: model.init(jax.random.PRNGKey(0), t),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
            check_vma=False)(toks)


def test_lm_ulysses_forward_matches_single():
    """dp2 x sp2 x tp2 with sp_mode='ulysses' == single-device forward
    (local heads after tp split: 4/2=2, divisible by sp=2)."""
    rng = np.random.RandomState(13)
    toks = jnp.asarray(rng.randint(0, 64, (4, 32)).astype(np.int32))

    ref_model = _tiny_lm()
    params = ref_model.init(jax.random.PRNGKey(1), toks[:1])["params"]
    want = ref_model.apply({"params": params}, toks)

    mesh = make_mesh(dp=2, sp=2, tp=2)
    sh_model = _tiny_lm(tp_axis="tp", sp_axis="sp", tp_size=2,
                        sp_mode="ulysses")
    specs = lm_param_specs(params, "tp")

    out = jax.jit(shard_map(
        lambda p, t: sh_model.apply({"params": p}, t),
        mesh=mesh, in_specs=(specs, P("dp", "sp")),
        out_specs=P("dp", "sp"), check_vma=False))(params, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def _tiny_lm(**kw):
    return transformer_lm(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                          d_ff=64, **kw)


def test_lm_forward_single_device():
    model = _tiny_lm()
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 16)))
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    logits = model.apply({"params": params}, toks)
    assert logits.shape == (2, 16, 64)
    assert np.isfinite(np.asarray(logits)).all()


def test_lm_sharded_forward_matches_single():
    """dp2 x sp2 x tp2 sharded forward == single-device forward."""
    rng = np.random.RandomState(3)
    toks = jnp.asarray(rng.randint(0, 64, (4, 32)).astype(np.int32))

    ref_model = _tiny_lm()
    params = ref_model.init(jax.random.PRNGKey(1), toks[:1])["params"]
    want = ref_model.apply({"params": params}, toks)

    mesh = make_mesh(dp=2, sp=2, tp=2)
    sh_model = _tiny_lm(tp_axis="tp", sp_axis="sp", tp_size=2)
    specs = lm_param_specs(params, "tp")

    def fwd(p, t):
        return sh_model.apply({"params": p}, t)

    out = jax.jit(shard_map(
        fwd, mesh=mesh, in_specs=(specs, P("dp", "sp")),
        out_specs=P("dp", "sp"), check_vma=False))(params, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_lm_train_step_dp_sp_tp():
    """Full quantized train step over dp2 x sp2 x tp2: runs, loss finite,
    params move, loss decreases over repeated steps on one batch."""
    from cpd_tpu.train import (create_train_state, make_lm_train_step,
                               make_optimizer)

    mesh = make_mesh(dp=2, sp=2, tp=2)
    model = _tiny_lm(tp_axis="tp", sp_axis="sp", tp_size=2)
    tx = make_optimizer("sgd", lambda s: 0.2, momentum=0.9)

    rng = np.random.RandomState(4)
    toks = jnp.asarray(rng.randint(0, 64, (4, 32)).astype(np.int32))
    tgts = jnp.roll(toks, -1, axis=1)

    # init params on the single-device module (global shapes)
    init_model = _tiny_lm()
    state = create_train_state(init_model, tx, toks[:1],
                               jax.random.PRNGKey(2))
    step = make_lm_train_step(model, tx, mesh, use_aps=True, grad_exp=5,
                              grad_man=2, mode="faithful", donate=False)
    state1, m1 = step(state, toks, tgts)
    assert np.isfinite(float(m1["loss"]))
    for _ in range(6):
        state1, m = step(state1, toks, tgts)
    assert float(m["loss"]) < float(m1["loss"])


@pytest.mark.slow
def test_lm_train_step_dp_sp_tp_chunked_gqa():
    """The full composition round 4 added, in one step: chunked
    attention (ring inner fold) + unexpanded GQA K/V + Megatron tp +
    quantized dp collective over dp2 x sp2 x tp2 — trains, and matches
    the same step with impl='xla' to fp32 round-off."""
    from cpd_tpu.train import (create_train_state, make_lm_train_step,
                               make_optimizer)

    mesh = make_mesh(dp=2, sp=2, tp=2)
    tx = make_optimizer("sgd", lambda s: 0.2, momentum=0.9)
    rng = np.random.RandomState(5)
    toks = jnp.asarray(rng.randint(0, 64, (4, 32)).astype(np.int32))
    tgts = jnp.roll(toks, -1, axis=1)
    init_model = _tiny_lm(n_kv_heads=2)
    state = create_train_state(init_model, tx, toks[:1],
                               jax.random.PRNGKey(2))

    def run(impl):
        model = _tiny_lm(tp_axis="tp", sp_axis="sp", tp_size=2,
                         n_kv_heads=2, attn_impl=impl)
        step = make_lm_train_step(model, tx, mesh, use_aps=True,
                                  grad_exp=5, grad_man=2,
                                  mode="faithful", donate=False)
        s, m = step(state, toks, tgts)
        return s, float(m["loss"])

    s_c, l_c = run("chunked")
    s_x, l_x = run("xla")
    assert np.isfinite(l_c)
    np.testing.assert_allclose(l_c, l_x, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s_c.params),
                    jax.tree.leaves(s_x.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_lm_step_rejects_norm_based_optimizer():
    """LARS trust ratios need global norms; the shard-local LM update must
    refuse it rather than silently compute per-shard norms."""
    from cpd_tpu.train import make_lm_train_step, make_optimizer

    mesh = make_mesh(dp=2, sp=2, tp=2)
    tx = make_optimizer("lars", lambda s: 0.1)
    with pytest.raises(ValueError, match="norm-based"):
        make_lm_train_step(_tiny_lm(), tx, mesh)


def test_lm_train_step_emulate_node():
    from cpd_tpu.train import (create_train_state, make_lm_train_step,
                               make_optimizer)

    mesh = make_mesh(dp=2, sp=2, tp=2)
    model = _tiny_lm(tp_axis="tp", sp_axis="sp", tp_size=2)
    tx = make_optimizer("sgd", lambda s: 0.1)

    rng = np.random.RandomState(5)
    toks = jnp.asarray(rng.randint(0, 64, (8, 32)).astype(np.int32))
    tgts = jnp.roll(toks, -1, axis=1)
    state = create_train_state(_tiny_lm(), tx, toks[:1],
                               jax.random.PRNGKey(3))
    step = make_lm_train_step(model, tx, mesh, emulate_node=2, use_aps=True,
                              grad_exp=5, grad_man=2, mode="fast",
                              donate=False)
    state, m = step(state, toks, tgts)
    assert np.isfinite(float(m["loss"]))
    assert 0.0 <= float(m["accuracy"]) <= 1.0


def test_lm_sharded_grads_match_single_device():
    """Regression for the tp-gradient-scaling bug: gradients computed
    through the dp/sp/tp-sharded loss (with the exact reduction path) must
    equal single-device gradients of the same global-mean loss — for every
    parameter, sharded and replicated alike."""
    import optax
    from cpd_tpu.models.transformer import lm_param_specs

    rng = np.random.RandomState(7)
    toks = jnp.asarray(rng.randint(0, 64, (4, 32)).astype(np.int32))
    tgts = jnp.roll(toks, -1, axis=1)

    ref_model = _tiny_lm()
    params = ref_model.init(jax.random.PRNGKey(5), toks[:1])["params"]

    def ref_loss(p):
        logits = ref_model.apply({"params": p}, toks)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgts).mean()

    g_ref = jax.grad(ref_loss)(params)

    mesh = make_mesh(dp=2, sp=2, tp=2)
    sh_model = _tiny_lm(tp_axis="tp", sp_axis="sp", tp_size=2)
    specs = lm_param_specs(params, "tp")

    def sharded_grads(p, tk, tg):
        def loss_of(p):
            logits = sh_model.apply({"params": p}, tk)
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, tg)
            n = lax.psum(jnp.float32(ce.size), ("dp", "sp", "tp"))
            return ce.sum() / n
        grads = jax.grad(loss_of)(p)

        def reduce(g, spec):
            g = lax.psum(g, "sp")
            if spec == P():
                g = lax.psum(g, "tp")
            return lax.psum(g, "dp")   # fp32 dp sum (loss pre-divided by n)

        return jax.tree.map(reduce, grads, specs)

    g_sh = jax.jit(shard_map(
        sharded_grads, mesh=mesh,
        in_specs=(specs, P("dp", "sp"), P("dp", "sp")),
        out_specs=specs, check_vma=False))(params, toks, tgts)

    flat_ref = jax.tree_util.tree_leaves_with_path(g_ref)
    flat_sh = dict(jax.tree_util.tree_leaves_with_path(g_sh))
    assert len(flat_ref) == len(flat_sh)
    for path, leaf in flat_ref:
        np.testing.assert_allclose(
            np.asarray(flat_sh[path]), np.asarray(leaf),
            rtol=2e-5, atol=1e-6, err_msg=str(path))


def test_flash_attention_impl_gating():
    """impl='flash' rejects offsets; on a TPU it must match the XLA path
    (skipped elsewhere — the Pallas TPU kernel doesn't run on CPU)."""
    from cpd_tpu.ops.attention import local_attention

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 128, 4, 128).astype(np.float32))
    with pytest.raises(ValueError, match="offsets"):
        local_attention(q, q, q, impl="flash", q_offset=4)

    if jax.default_backend() != "tpu":
        pytest.skip("Pallas TPU flash kernel needs a TPU")
    want = np.asarray(local_attention(q, q, q, causal=True))
    got = np.asarray(local_attention(q, q, q, causal=True, impl="flash"))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_block_gqa_flash_pallas_bwd_matches_xla():
    """Block with attn_impl='flash' + GQA: forward AND parameter
    gradients match the attn_impl='xla' block on the same params — the
    model-level composition of the GQA-native kernel with its Pallas
    backward (CLI: --attn-impl flash --n-kv-heads)."""
    from cpd_tpu.models.transformer import Block

    def blk(impl):
        # 4 q heads over 2 kv heads — genuinely grouped, so the flash
        # route lands on the in-repo GQA kernel, not the stock MHA one
        return Block(head_dim=32, d_ff=64, d_model=128, tp_axis=None,
                     sp_axis=None, tp_size=1, dtype=jnp.float32,
                     n_kv_heads=2, attn_impl=impl)

    rng = np.random.RandomState(17)
    h = jnp.asarray(rng.randn(1, 64, 128).astype(np.float32))
    pos = jnp.arange(64)
    vb = blk("xla").init(jax.random.PRNGKey(6), h, pos)

    def loss(impl):
        return lambda p: jnp.sum(
            blk(impl).apply({"params": p}, h, pos) ** 2)

    out_x = blk("xla").apply(vb, h, pos)
    out_f = blk("flash").apply(vb, h, pos)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_x),
                               rtol=2e-5, atol=2e-5)
    gx = jax.grad(loss("xla"))(vb["params"])
    gf = jax.grad(loss("flash"))(vb["params"])
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(gf)[0],
            jax.tree_util.tree_flatten_with_path(gx)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5,
                                   err_msg=str(path))


def test_lm_decode_cache_overflow_poisons_with_nan():
    """The documented overflow contract (transformer.py decode docstring,
    ADVICE r2): a write past the allocated cache length cannot raise from
    inside jit, so the step's outputs must be all-NaN — never a silently
    clamped write that argmax would turn into plausible tokens."""
    model = _tiny_lm(decode=True)
    toks = jnp.asarray(np.random.RandomState(5).randint(
        0, 64, (1, 6)).astype(np.int32))
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32), train=False)
    params, cache = variables["params"], variables["cache"]

    # prefill 6 of 8 slots — well-formed
    logits, vs = model.apply({"params": params, "cache": cache}, toks,
                             train=False, mutable=["cache"])
    assert not np.isnan(np.asarray(logits)).any()
    cache = vs["cache"]

    # two more single-token steps fill slots 6 and 7; the third writes
    # position 8 == t_max and must poison
    tok = jnp.zeros((1, 1), jnp.int32)
    for step in range(3):
        logits, vs = model.apply({"params": params, "cache": cache}, tok,
                                 train=False, mutable=["cache"])
        cache = vs["cache"]
        nans = np.isnan(np.asarray(logits))
        if step == 2:
            assert nans.all(), "overflow step must poison every logit"
        else:
            assert not nans.any(), f"in-bounds step {step} produced NaN"


@pytest.mark.slow  # second full sharded-LM compile; QuantDense mechanics are fast-tier in test_quant_module
def test_lm_quantized_ffn():
    """ffn_exp/ffn_man route the MLP pair through the quantized GEMM:
    same param tree as the unquantized model (checkpoint compatible),
    different logits at e4m3, gradients finite — and the composition
    holds under tp sharding."""
    toks = jnp.asarray(np.random.RandomState(77).randint(
        0, 64, (4, 8)).astype(np.int32))
    plain = _tiny_lm()
    quant = _tiny_lm(ffn_exp=4, ffn_man=3)
    params = plain.init(jax.random.PRNGKey(0), toks)["params"]
    # identical tree: QuantDense keeps Dense's kernel name/layout
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(
                quant.init(jax.random.PRNGKey(0), toks)["params"]))

    out_plain = plain.apply({"params": params}, toks)
    out_quant = quant.apply({"params": params}, toks)
    assert np.isfinite(np.asarray(out_quant)).all()
    assert np.abs(np.asarray(out_quant) - np.asarray(out_plain)).max() > 1e-4

    import optax

    def loss(p):
        logits = quant.apply({"params": p}, toks)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.roll(toks, -1, axis=1)).mean()

    grads = jax.grad(loss)(params)
    for leaf in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(leaf)).all()

    # tp2 composition: per-shard quantized accumulation + fp32 psum
    from cpd_tpu.train import create_train_state, make_lm_train_step, \
        make_optimizer

    mesh = make_mesh(dp=4, tp=2)
    sh = _tiny_lm(ffn_exp=4, ffn_man=3, tp_axis="tp", tp_size=2)
    tx = make_optimizer("sgd", lambda s: 0.1)
    state = create_train_state(_tiny_lm(ffn_exp=4, ffn_man=3), tx,
                               toks[:1], jax.random.PRNGKey(2))
    step = make_lm_train_step(sh, tx, mesh, donate=False)
    _, m = step(state, toks, jnp.roll(toks, -1, axis=1))
    assert np.isfinite(float(m["loss"]))
