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


@pytest.mark.parametrize("onto,pairs,load", [
    ((2, 0, 1), 32.0, 2.0),     # one held expert takes every token
    ((2, 3, 0), 64.0, 1.0),     # both held experts take every token
    ((0, 1, 4), 0.0, 0.0),      # absent experts only
], ids=["one_held", "both_held", "absent_only"])
def test_experts_forced_imbalance_drops_nothing(onto, pairs, load):
    """Every token forced onto chosen experts through the selection bias:
    no row is dropped (the output is the reference's, whose dense passes
    cannot drop), the counters are exact, and absent experts add zero."""
    layer, params, h, _, _ = _expert_case()
    params = {**params, "score_bias": _bias_towards(onto)}
    out, sown = layer.apply({"params": params}, h, mutable=[mm.COUNTERS])
    counts = {k: float(v[0]) for k, v in sown[mm.COUNTERS].items()}
    assert counts == {"moe_pairs_held": pairs,
                      "moe_load_max_over_mean": load}
    want = _ref_routed(h, params, 2, 2)
    if pairs:
        assert rel(out, want) < 1e-5
        assert float(jnp.abs(out).min(-1).max()) > 0   # every token served
    else:
        assert float(jnp.abs(out).max()) == 0.0 == float(jnp.abs(want).max())
    # the bias has no gradient, the router and the held experts do
    g = jax.grad(lambda p: jnp.sum(layer.apply(
        {"params": p}, h, mutable=[mm.COUNTERS])[0] ** 2))(params)
    assert float(jnp.abs(g["score_bias"]).max()) == 0.0
    assert (float(jnp.abs(g["experts_down"]).max()) > 0) == bool(pairs)


def test_rows_past_the_held_count_never_reach_a_gradient():
    """A grouped product leaves the rows past its groups unwritten, in the
    product and in the backward's (`ops/grouped.py`; the interpreter
    leaves NaN there, a TPU what the memory held).  Gradients with
    respect to parameters AND inputs are finite and the reference's."""
    from cpd_tpu.ops.grouped import grouped_matmul
    x = jax.random.normal(jax.random.PRNGKey(0), (96, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 24))
    sizes = jnp.asarray([20, 13], jnp.int32)
    got = grouped_matmul(x, w, sizes)
    np.testing.assert_allclose(got[:33], jax.lax.ragged_dot(x, w, sizes)[:33],
                               atol=1e-5)
    assert not bool(jnp.isfinite(got[33:]).any())   # what a caller masks

    layer, params, h, _, _ = _expert_case()

    def loss(p, h):
        return jnp.sum(layer.apply({"params": p}, h,
                                   mutable=[mm.COUNTERS])[0] ** 2)

    got = jax.grad(loss, (0, 1))(params, h)
    want = jax.grad(lambda p, h: jnp.sum(_ref_routed(h, p, 2, 2) ** 2),
                    (0, 1))(params, h)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.isfinite(a).all())
        assert rel(a, b) < 1e-5 or float(jnp.abs(b).max()) == 0.0


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


@pytest.mark.parametrize("dp", [1, 4])
def test_step_with_e5m2_aps_reports_the_counters(dp):
    """The same entry point as the dense LM cells, e5m2 APS, on 1 and on 4
    devices over `dp`: the loss is the reference's, the counters are in
    the metrics and are the whole batch's, the update is near the
    reference's SGD step (e5m2's rounding: 0.053 of an element)."""
    model = model_of(remat=True)
    mesh = make_mesh(dp=dp, devices=jax.devices()[:dp])
    tx = make_optimizer("sgd", lambda step: 0.01, momentum=0.9,
                        weight_decay=0.0)
    a, b = batch(b=4)
    state = _state(model, tx, a)
    step = make_lm_train_step(model, tx, mesh, use_aps=True, grad_exp=5,
                              grad_man=2, mode="faithful", donate=False)
    new, metrics = step(state, a, b)
    want_loss, g = REF_GRAD(state.params, a, b)
    assert abs(float(metrics["loss"]) - float(want_loss)) < 1e-5
    # pairs held: every expert layer, every sequence, by the router itself
    _, sown = model.apply({"params": state.params}, a, mutable=[mm.COUNTERS])
    by_layer = [float(b["moe"]["moe_pairs_held"][0])
                for b in sown[mm.COUNTERS].values()]
    assert float(metrics["moe_pairs_held"]) == sum(by_layer) > 0
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0
    moved = jax.tree.map(lambda n, o, gg: (n - o, -0.01 * gg), new.params,
                         state.params, g)
    num = sum(float(jnp.sum((d - w) ** 2)) for d, w in
              jax.tree.leaves(moved, is_leaf=lambda x: isinstance(x, tuple)))
    den = sum(float(jnp.sum(w ** 2)) for _, w in
              jax.tree.leaves(moved, is_leaf=lambda x: isinstance(x, tuple)))
    assert (num / den) ** 0.5 < (0.08 if dp == 1 else 0.16)


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
