"""Latent attention and dropless routed experts (models/mla_moe.py), v of
its own width in the attention ops, and a model's counters in the LM
step's metrics (train/lm.py).

The oracle is the benchmark's plain float32 reference
(`benchmark/reference/mla_moe_lm.py`), which shares no code with the
model.  CPU, tiny sizes, float32 compute unless a test says otherwise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import mla_moe_lm as ref
from cpd_tpu.models import mla_moe as mm
from cpd_tpu.models import mla_moe_lm, transformer_lm
from cpd_tpu.ops.attention import _chunked_attention, local_attention
from cpd_tpu.ops.flash_gqa import flash_gqa
from cpd_tpu.parallel.mesh import make_mesh
from cpd_tpu.train import make_lm_train_step, make_optimizer
from cpd_tpu.train.state import TrainState
from flash_remat import compare_with_bare_remat

# a tiny cut of the DeepSeek-V3 block: the reference's (published) keys
CFG = dict(hidden_size=32, num_attention_heads=4, kv_lora_rank=16,
           qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=6,
           intermediate_size=48, moe_intermediate_size=24,
           n_routed_experts=2, n_routed_experts_published=8, expert_first=2,
           num_experts_per_tok=3, n_shared_experts=2,
           routed_scaling_factor=2.446, rope_theta=50000,
           rms_norm_eps=1e-5, first_k_dense_replace=1, num_hidden_layers=2,
           vocab_size=64)


def model_of(cfg=CFG, **kw):
    return mla_moe_lm(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], d_ff=cfg["intermediate_size"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        first_dense=cfg["first_k_dense_replace"],
        n_experts=cfg["n_routed_experts_published"],
        experts_held=cfg["n_routed_experts"],
        expert_first=cfg["expert_first"], top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling=cfg["routed_scaling_factor"],
        **{"init_std": 0.2, **kw})


def batch(seed=1, b=2, t=16, vocab=64):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (b, t + 1), 0, vocab)
    return toks[:, :-1], toks[:, 1:]


REF_LOSS = jax.jit(lambda p, a, b: ref.loss(p, a, b, CFG))
REF_GRAD = jax.jit(jax.value_and_grad(lambda p, a, b: ref.loss(p, a, b, CFG)))


def rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


# ---- attention ops: v of its own width ---------------------------------

def _qkv(h, hkv, d, dv, tq=40, tk=40):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(ks[0], (2, tq, h, d)),
            jax.random.normal(ks[1], (2, tk, hkv, d)),
            jax.random.normal(ks[2], (2, tk, hkv, dv)))


def _oracle(q, k, v, causal=True):
    rep = q.shape[2] // k.shape[2]
    return local_attention(q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2),
                           causal=causal)


IMPLS = {"flash": lambda q, k, v: flash_gqa(q, k, v, True),
         "chunked": lambda q, k, v: _chunked_attention(q, k, v, True, 0, 0)}


@pytest.mark.parametrize("impl", sorted(IMPLS))
@pytest.mark.parametrize("h,hkv,d,dv", [(4, 2, 24, 16), (4, 4, 12, 8),
                                        (4, 2, 16, 16), (2, 2, 8, 24)])
def test_attention_with_v_of_its_own_width(impl, h, hkv, d, dv):
    """Forward and gradient against `local_attention` on expanded K/V, at
    d_v != d_qk and at the equal widths the ops had."""
    q, k, v = _qkv(h, hkv, d, dv)
    got = IMPLS[impl](q, k, v)
    assert got.shape == (2, 40, h, dv)
    np.testing.assert_allclose(got, _oracle(q, k, v), atol=2e-6)
    loss = lambda f: lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))
    gs = jax.grad(loss(IMPLS[impl]), (0, 1, 2))(q, k, v)
    go = jax.grad(loss(_oracle), (0, 1, 2))(q, k, v)
    for a, b in zip(gs, go):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-6)


def test_flash_long_block_for_a_narrow_group_matches_short_rows():
    """rep 1 takes q blocks of 8 x 128 rows; results do not depend on it."""
    q, k, v = _qkv(2, 2, 12, 8, tq=300, tk=300)
    np.testing.assert_allclose(flash_gqa(q, k, v), _oracle(q, k, v),
                               atol=2e-6)


# ---- latent attention ---------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "chunked", "flash"])
def test_latent_attention_matches_reference(impl):
    attn = mm.LatentAttention(4, 16, 8, 4, 6, rope_theta=50000.0,
                              attn_impl=impl, init_std=0.2)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 32))
    params = attn.init(jax.random.PRNGKey(0), h, jnp.arange(16))["params"]
    got = attn.apply({"params": params}, h, jnp.arange(16))
    want = jnp.stack([ref._attention(h[i], params, CFG) for i in range(2)])
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("impl", ["flash", "chunked"])
def test_latent_attention_parameter_gradient_matches_xla(impl):
    """Through the flash kernels' own backward (q/k 12 wide, v 6) and
    through the scan's, against `attn_impl="xla"` on the same parameters."""
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 32))
    pos = jnp.arange(16)
    attn = lambda impl: mm.LatentAttention(
        4, 16, 8, 4, 6, rope_theta=50000.0, attn_impl=impl, init_std=0.2)
    params = attn("xla").init(jax.random.PRNGKey(0), h, pos)["params"]
    grad = lambda impl: jax.grad(lambda p: jnp.sum(jnp.sin(
        attn(impl).apply({"params": p}, h, pos))))(params)
    got, want = grad(impl), grad("xla")
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        assert rel(a, b) < 1e-5, path


# ---- the routed experts -------------------------------------------------

def _experts(held=2, first=2, n=8, k=3):
    return mm.RoutedExperts(n, held, first, k, 24, routed_scaling=2.446,
                            init_std=0.2)


def _expert_case(**kw):
    layer = _experts(**kw)
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 32))
    params = layer.init(jax.random.PRNGKey(0), h)["params"]
    out, sown = layer.apply({"params": params}, h, mutable=[mm.COUNTERS])
    counts = {k: float(v[0]) for k, v in sown[mm.COUNTERS].items()}
    return layer, params, h, out, counts


def _ref_routed(h, params, held, first):
    cfg = {**CFG, "n_routed_experts": held, "expert_first": first}
    return jnp.stack([ref._routed(h[i], params, cfg)
                      for i in range(h.shape[0])])


def test_experts_seeded_routing_matches_reference_and_counts():
    _, params, h, out, counts = _expert_case()
    assert rel(out, _ref_routed(h, params, 2, 2)) < 1e-5
    s = jax.nn.sigmoid(h.reshape(-1, 32) @ params["router"])
    chosen = jax.lax.top_k(s, 3)[1]
    per_expert = [(chosen == e).sum() for e in (2, 3)]
    assert counts["moe_pairs_held"] == float(sum(per_expert))
    assert counts["moe_load_max_over_mean"] == pytest.approx(
        float(max(per_expert)) / (float(sum(per_expert)) / 2))


def _bias_towards(experts, n=8):
    """A selection bias that puts `experts` on top for every token (the
    scores are sigmoids, under 1): routing is forced, gates are not."""
    return jnp.zeros((n,)).at[jnp.asarray(experts)].set(10.0)


# 640 tokens x 3 slots on a chip that holds experts 2 and 3 of 16: 1,920
# pairs, 240 held by expectation, so the `d`-wide work is bounded by
# C = 512 rows (`_row_bound`: twice 240, in whole row tiles) and a step
# that holds more runs over all 1,920
WIDE = dict(held=2, first=2, n=16, k=3)
WIDE_TOKENS, WIDE_C = (2, 320), 512


def _wide_case(onto=None, two=0, one=0):
    """(layer, params, h) with the routing forced: by the selection bias
    `onto` those experts for every token, or, token by token, `two`
    tokens onto both held experts, `one` onto expert 2 alone and the rest
    onto absent ones (two input features the router reads with weights of
    4 where its others are 0.02: scores of 0.98 and 0.02, so the gates
    keep a gradient) -- 2 x two + one pairs held, to the pair."""
    layer = _experts(**WIDE)
    h = jax.random.normal(jax.random.PRNGKey(5), (*WIDE_TOKENS, 32))
    params = layer.init(jax.random.PRNGKey(0), h)["params"]
    if onto is not None:
        return layer, {**params, "score_bias": _bias_towards(onto, 16)}, h
    if not two and not one:
        return layer, params, h                     # as seeded
    tokens = h.shape[0] * h.shape[1]
    kind = jax.random.permutation(jax.random.PRNGKey(6), jnp.asarray(
        [2] * two + [1] * one + [0] * (tokens - two - one))
    ).reshape(h.shape[:2])
    h = h.at[..., 0].set(jnp.where(kind == 2, 1.0,
                                   jnp.where(kind == 0, -1.0, 0.0)))
    h = h.at[..., 1].set(jnp.where(kind == 1, 1.0, 0.0))
    router = 0.02 * jax.random.normal(jax.random.PRNGKey(7), (32, 16))
    router = router.at[:2].set(0.0).at[0, 2:4].set(4.0)
    router = router.at[1, 2].set(4.0).at[1, 3].set(-4.0)
    return layer, {**params, "router": router}, h


def _apply(layer, params, h):
    out, sown = jax.jit(lambda p, h: layer.apply(
        {"params": p}, h, mutable=[mm.COUNTERS]))(params, h)
    return out, {k: float(v[0]) for k, v in sown[mm.COUNTERS].items()}


# compact: 1.0 where the step must run over C rows, 0.0 where it must run
# over all T·k (the fallback, or a layer with no bound below T·k)
@pytest.mark.parametrize("wide,onto,pairs,load,compact", [
    (False, (2, 0, 1), 32.0, 2.0, 0.0),  # one held expert takes every token
    (False, (2, 3, 0), 64.0, 1.0, 0.0),  # both held experts take every token
    (False, (0, 1, 4), 0.0, 0.0, 0.0),   # absent experts only
    (True, (2, 0, 1), 640.0, 2.0, 0.0),  # 640 > C: every row, on T·k rows
    (True, (2, 3, 0), 1280.0, 1.0, 0.0),
    (True, (0, 1, 4), 0.0, 0.0, 1.0),    # nothing held fits any bound
], ids=["one_held", "both_held", "absent_only", "wide_one_held_fallback",
        "wide_both_held_fallback", "wide_absent_only_compact"])
def test_experts_forced_imbalance_drops_nothing(wide, onto, pairs, load,
                                                compact):
    """Every token forced onto chosen experts through the selection bias:
    no row is dropped (the output is the reference's, whose dense passes
    cannot drop), the counters are exact, absent experts add zero, and
    the step took the branch the case names: a layer of 2 held experts of
    8 at 96 pairs has no bound below them; the wide layer (`WIDE`) bounds
    its rows by C = 512 and holds 640 and 1,280 pairs here."""
    if wide:
        layer, params, h = _wide_case(onto=onto)
    else:
        layer, params, h, _, _ = _expert_case()
        params = {**params, "score_bias": _bias_towards(onto)}
    out, counts = _apply(layer, params, h)
    assert counts == {"moe_pairs_held": pairs,
                      "moe_load_max_over_mean": load,
                      "moe_compact": compact}
    want = _ref_routed(h, params, 2, 2)
    if pairs:
        assert rel(out, want) < 1e-5
        assert float(jnp.abs(out).min(-1).max()) > 0   # every token served
    else:
        assert float(jnp.abs(out).max()) == 0.0 == float(jnp.abs(want).max())
    # the bias has no gradient, the router and the held experts do
    g = jax.jit(jax.grad(lambda p: jnp.sum(layer.apply(
        {"params": p}, h, mutable=[mm.COUNTERS])[0] ** 2)))(params)
    assert float(jnp.abs(g["score_bias"]).max()) == 0.0
    assert (float(jnp.abs(g["experts_down"]).max()) > 0) == bool(pairs)


def _grads_match_reference(layer, params, h):
    """Gradients with respect to parameters (router, all three expert
    tensors) AND tokens are finite and the reference's."""
    def loss(p, h):
        return jnp.sum(layer.apply({"params": p}, h,
                                   mutable=[mm.COUNTERS])[0] ** 2)

    got = jax.jit(jax.grad(loss, (0, 1)))(params, h)
    want = jax.jit(jax.grad(
        lambda p, h: jnp.sum(_ref_routed(h, p, 2, 2) ** 2), (0, 1)))(
            params, h)
    for name in ("router", "experts_gate", "experts_up", "experts_down"):
        assert float(jnp.abs(want[0][name]).max()) > 0, name
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        assert bool(jnp.isfinite(a).all()), path
        assert rel(a, b) < 1e-5 or float(jnp.abs(b).max()) == 0.0, path


@pytest.mark.parametrize("two,one,compact", [
    (255, 1, 1.0),      # C - 1 pairs held
    (256, 0, 1.0),      # C: the last row of the buffer is live
    (256, 1, 0.0),      # C + 1: one pair too many for it, so all T·k rows
    (0, 0, 1.0),        # as seeded: about 240
], ids=["bound_less_one", "at_bound", "bound_plus_one", "seeded"])
def test_compact_and_fallback_match_reference_around_the_bound(two, one,
                                                               compact):
    """The branch is chosen by the step's own count, to the pair, and both
    give the reference's output and gradients."""
    layer, params, h = _wide_case(two=two, one=one)
    out, counts = _apply(layer, params, h)
    if two or one:
        assert counts["moe_pairs_held"] == 2 * two + one
    else:
        assert 0 < counts["moe_pairs_held"] < WIDE_C
    assert counts["moe_compact"] == compact
    assert rel(out, _ref_routed(h, params, 2, 2)) < 1e-5
    _grads_match_reference(layer, params, h)


@pytest.mark.parametrize("pairs,held,n_experts,bound", [
    (98304, 8, 64, 24576),      # the Moonlight cell: a quarter of T·k
    (98304, 32, 64, 98304),     # a share of a half: no bound below T·k
    (98304, 64, 64, 98304),
    (96, 2, 8, 96),             # under one row tile
    (768, 2, 8, 512), (1920, 2, 16, 512), (2304, 2, 8, 1536),
    (98304, 1, 64, 3072), (100000, 8, 64, 25088),
])
def test_row_bound(pairs, held, n_experts, bound):
    """Twice the pairs held by expectation, in whole row tiles of the
    grouped product (512), and never over the dropless T·k."""
    assert mm._row_bound(pairs, held, n_experts) == bound
    assert bound == pairs or (bound % 512 == 0
                              and bound >= 2 * pairs * held / n_experts)


def _conds(jaxpr) -> int:
    """`cond` equations of a jaxpr, nested ones too, a Pallas kernel's own
    left out (the interpreter's `pl.when`)."""
    from jax.extend import core as jex
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        count += eqn.primitive.name == "cond"
        for sub in jax.tree.leaves(
                list(eqn.params.values()),
                is_leaf=lambda v: isinstance(v, (jex.Jaxpr, jex.ClosedJaxpr))):
            if isinstance(sub, (jex.Jaxpr, jex.ClosedJaxpr)):
                count += _conds(getattr(sub, "jaxpr", sub))
    return count


@pytest.mark.parametrize("held,n,tokens,branches", [
    (8, 8, (2, 16), 0),     # every expert held: nothing to bound
    (8, 16, WIDE_TOKENS, 0),    # a share of a half
    (2, 8, (2, 16), 0),     # 96 pairs: under one row tile
    (2, 16, WIDE_TOKENS, 1),
])
def test_a_layer_with_no_bound_below_the_pairs_traces_without_a_branch(
        held, n, tokens, branches):
    """Where C == T·k the module is the program it was (and the counter):
    no conditional of its own; where C < T·k there is one.  (Read from the
    jaxpr: the lowered text of a CPU run holds the kernels' interpreter,
    whose `pl.when`s are conditionals too.)"""
    layer = _experts(held=held, first=0, n=n)
    h = jax.ShapeDtypeStruct((*tokens, 32), jnp.float32)
    params = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros(h.shape)))["params"]
    jaxpr = jax.make_jaxpr(lambda p, h: layer.apply(
        {"params": p}, h, mutable=[mm.COUNTERS]))(params, h)
    assert _conds(jaxpr.jaxpr) == branches


@pytest.mark.parametrize("case", ["no_bound", "compact", "fallback"])
def test_rows_past_the_held_count_never_reach_a_gradient(case):
    """A grouped product leaves the rows past its groups unwritten, in the
    product and in the backward's (`ops/grouped.py`; the interpreter
    leaves NaN there, a TPU what the memory held).  Gradients with
    respect to parameters AND inputs are finite and the reference's, over
    T·k rows and over C, in both branches."""
    from cpd_tpu.ops.grouped import grouped_matmul
    x = jax.random.normal(jax.random.PRNGKey(0), (96, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 24))
    sizes = jnp.asarray([20, 13], jnp.int32)
    got = grouped_matmul(x, w, sizes)
    np.testing.assert_allclose(got[:33], jax.lax.ragged_dot(x, w, sizes)[:33],
                               atol=1e-5)
    assert not bool(jnp.isfinite(got[33:]).any())   # what a caller masks

    if case == "no_bound":
        layer, params, h, _, counts = _expert_case()
        compact = 0.0
    else:
        # seeded routing leaves half of the C rows dead; 600 pairs held
        # leave 1,320 of the T·k rows dead
        layer, params, h = _wide_case(**({} if case == "compact"
                                         else dict(two=300)))
        compact = float(case == "compact")
        counts = _apply(layer, params, h)[1]
    assert counts["moe_compact"] == compact
    _grads_match_reference(layer, params, h)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of the 4 shares of an 8-expert layer (2 experts
    each) sum to the reference's uncut 8-expert layer."""
    whole = _experts(held=8, first=0)
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 32))
    params = whole.init(jax.random.PRNGKey(0), h)["params"]
    want = _ref_routed(h, params, 8, 0)
    total, pairs = 0.0, 0.0
    for first in range(0, 8, 2):
        share = {k: (v[first:first + 2] if k.startswith("experts_") else v)
                 for k, v in params.items()}
        out, sown = _experts(held=2, first=first).apply(
            {"params": share}, h, mutable=[mm.COUNTERS])
        total = total + out
        pairs += float(sown[mm.COUNTERS]["moe_pairs_held"][0])
    assert rel(total, want) < 1e-5
    assert pairs == 2 * 16 * 3          # every pair is held by one share


def test_expert_range_is_checked():
    with pytest.raises(ValueError, match="not among"):
        _experts(held=4, first=6).init(jax.random.PRNGKey(0),
                                       jnp.zeros((1, 4, 32)))


# ---- the whole model ----------------------------------------------------

def _loss_of(model, a, b):
    def loss(p):
        logits = model.apply({"params": p}, a, mutable=[mm.COUNTERS])[0]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b).mean()
    return loss


@pytest.mark.parametrize("impl,remat", [("xla", False), ("flash", True)])
def test_model_loss_and_gradient_match_reference(impl, remat):
    model = model_of(attn_impl=impl, remat=remat)
    a, b = batch()
    params = model_of().init(jax.random.PRNGKey(0), a)["params"]
    l1, g1 = jax.jit(jax.value_and_grad(_loss_of(model, a, b)))(params)
    l2, g2 = REF_GRAD(params, a, b)
    assert abs(float(l1) - float(l2)) < 1e-5 * float(l2)
    worst = max(jax.tree.leaves(jax.tree.map(rel, g1, g2)))
    assert worst < 1e-5
    # the selection bias: a leaf with no gradient, on both sides
    assert float(jnp.abs(g1["block1"]["moe"]["score_bias"]).max()) == 0.0
    assert float(jnp.abs(g2["block1"]["moe"]["score_bias"]).max()) == 0.0


@pytest.mark.parametrize("impl,blocks", [("flash", 2), ("xla", 0)])
def test_a_recomputed_block_runs_the_forward_kernel_once(impl, blocks,
                                                         monkeypatch):
    """Both blocks have attention (`tests/flash_remat.py` says what is
    held against the bare `nn.remat`)."""
    a, b = batch()
    params = model_of().init(jax.random.PRNGKey(0), a)["params"]
    compare_with_bare_remat(
        monkeypatch, mm, _loss_of(model_of(attn_impl=impl, remat=True), a, b),
        params, blocks)


def test_model_in_bfloat16_is_near_the_reference():
    """bf16 activations: 8 bits of mantissa through 2 layers, and a token
    whose 3rd and 4th scores lie within bf16's noise routes otherwise, so
    the loss is held to 2% and not to round-off."""
    a, b = batch()
    params = model_of().init(jax.random.PRNGKey(0), a)["params"]
    l1 = jax.jit(_loss_of(model_of(dtype=jnp.bfloat16), a, b))(params)
    assert abs(float(l1) - float(REF_LOSS(params, a, b))) < 0.02 * float(l1)


def test_parameter_names_are_not_tensor_parallel_ones():
    from cpd_tpu.models.transformer import lm_param_specs
    from jax.sharding import PartitionSpec as P
    params = jax.eval_shape(
        lambda: model_of().init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 8), jnp.int32)))["params"]
    assert sorted(params) == ["block0", "block1", "embed", "lm_head",
                              "norm_f"]
    specs = jax.tree.leaves(lm_param_specs(params),
                            is_leaf=lambda s: isinstance(s, P))
    assert specs and all(s == P() for s in specs)


def test_factory_refuses_fewer_key_heads():
    with pytest.raises(ValueError, match="n_kv_heads"):
        mla_moe_lm(n_heads=8, n_kv_heads=2)


def test_factory_drops_the_benchmark_files_backward_key():
    """`benchmark/configs/moonlight_16b_a3b_ep8_d5.json` still hands the
    factory the key that once chose attention's backward (ROADMAP D11)."""
    assert (mla_moe_lm(n_heads=8, attn_impl="flash", flash_bwd="chunked")
            == mla_moe_lm(n_heads=8, attn_impl="flash"))
    assert "flash_bwd" not in mm.MLAMoELM.__dataclass_fields__


# ---- through make_lm_train_step -----------------------------------------

def _state(model, tx, a):
    params = model.init(jax.random.PRNGKey(0), a[:1, :8])["params"]
    return TrainState(step=jnp.zeros([], jnp.int32), params=params,
                      batch_stats={}, opt_state=tx.init(params))


def _aps_step(model, dp, a):
    mesh = make_mesh(dp=dp, devices=jax.devices()[:dp])
    tx = make_optimizer("sgd", lambda step: 0.01, momentum=0.9,
                        weight_decay=0.0)
    state = _state(model, tx, a)
    return state, make_lm_train_step(
        model, tx, mesh, use_aps=True, grad_exp=5, grad_man=2,
        mode="faithful", donate=False)


# 16-token sequences: 2 of 8 experts held, 192 and 48 pairs a rank, under
# one row tile, so the layer has no bound below them and reports 0; at 192
# tokens a sequence a rank bounds its 2,304 pairs by 1,536 rows and its 576
# by 512, holds a quarter of them by expectation and reports 1
@pytest.mark.parametrize("dp,t,compact", [(1, 16, 0.0), (4, 16, 0.0),
                                          (1, 192, 1.0), (4, 192, 1.0)])
def test_step_with_e5m2_aps_reports_the_counters(dp, t, compact):
    """The same entry point as the dense LM cells, e5m2 APS, on 1 and on 4
    devices over `dp`: the loss is the reference's, the counters are in
    the metrics and are the whole batch's, the update is near the
    reference's SGD step (e5m2's rounding: 0.053 of an element)."""
    model = model_of(remat=True)
    a, b = batch(b=4, t=t)
    state, step = _aps_step(model, dp, a)
    new, metrics = step(state, a, b)
    want_loss, g = REF_GRAD(state.params, a, b)
    assert abs(float(metrics["loss"]) - float(want_loss)) < 1e-5
    # pairs held: every expert layer, every sequence, by the router itself
    _, sown = model.apply({"params": state.params}, a, mutable=[mm.COUNTERS])
    by_layer = [float(b["moe"]["moe_pairs_held"][0])
                for b in sown[mm.COUNTERS].values()]
    assert float(metrics["moe_pairs_held"]) == sum(by_layer) > 0
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0
    assert float(metrics["moe_compact"]) == compact
    moved = jax.tree.map(lambda n, o, gg: (n - o, -0.01 * gg), new.params,
                         state.params, g)
    num = sum(float(jnp.sum((d - w) ** 2)) for d, w in
              jax.tree.leaves(moved, is_leaf=lambda x: isinstance(x, tuple)))
    den = sum(float(jnp.sum(w ** 2)) for _, w in
              jax.tree.leaves(moved, is_leaf=lambda x: isinstance(x, tuple)))
    assert (num / den) ** 0.5 < (0.08 if dp == 1 else 0.16)


@pytest.mark.parametrize("t,branches", [(16, 0), (128, 3)])
def test_a_tiny_step_at_2_of_8_experts_held_traces_the_branch(t, branches):
    """768 pairs bounded by 512 rows: the whole step (loss, `nn.remat`,
    gradient, APS, update) holds the layer's conditional three times: the
    forward's, the one jax's partial evaluation leaves of `nn.remat`'s
    recomputation (its branches hand their operands on and compute
    nothing: each saves only those), and the backward's, which recomputes
    and transposes inside the branch taken; at 96 pairs it holds none."""
    model = model_of(remat=True)
    a, b = batch(b=2, t=t)
    state, step = _aps_step(model, 1, a)
    assert _conds(jax.make_jaxpr(step)(state, a, b).jaxpr) == branches


def test_counter_plumbing_leaves_a_dense_lm_step_as_it_was():
    """A model that declares no counters: the traced step is the same
    with and without the plumbing (here: a `TransformerLM` against the
    same model wearing an EMPTY `step_counters`, which takes the
    plumbing's branch-free path), equation for equation."""
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    tx = make_optimizer("sgd", lambda step: 0.01, momentum=0.9)
    model = transformer_lm(vocab_size=64, d_model=32, n_layers=1, n_heads=4,
                           n_kv_heads=2, d_ff=64)
    toks = jnp.zeros((2, 16), jnp.int32)
    state = _state(model, tx, toks)

    def jaxpr_of(m):
        step = make_lm_train_step(m, tx, mesh, use_aps=True, grad_exp=5,
                                  grad_man=2, donate=False)
        return str(jax.make_jaxpr(step)(state, toks, toks))

    plain = jaxpr_of(model)
    assert "moe_" not in plain
    declared_none = model.clone()
    object.__setattr__(declared_none, "step_counters", {})
    assert jaxpr_of(declared_none) == plain
    assert sorted(jax.eval_shape(
        make_lm_train_step(model, tx, mesh, donate=False),
        state, toks, toks)[1]) == ["accuracy", "loss"]


def test_unknown_counter_merge_is_refused():
    model = model_of()
    object.__setattr__(model, "step_counters", {"moe_pairs_held": "median"})
    with pytest.raises(ValueError, match="unknown merge"):
        make_lm_train_step(model, make_optimizer("sgd", lambda s: 0.01),
                           make_mesh(dp=1, devices=jax.devices()[:1]))
