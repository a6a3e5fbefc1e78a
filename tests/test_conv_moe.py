"""The hybrid short-convolution / attention mixture-of-experts LM
(models/conv_moe.py): gated depthwise causal convolutions mixed with
QK-normed grouped-query attention, routed experts with no shared one, a
tied head, trained through `make_lm_train_step`.

The oracle is the benchmark's plain float32 reference
(`benchmark/reference/conv_moe_lm.py`), which shares no code with the
model.  CPU, tiny sizes, float32 compute unless a test says otherwise.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import conv_moe_lm as ref
from benchmark.reference import mla_moe_lm as mla_ref
from cpd_tpu.models import conv_moe as cm
from cpd_tpu.models import conv_moe_lm, get_model
from cpd_tpu.models.mla_moe import COUNTERS
from cpd_tpu.obs import scopes
from cpd_tpu.parallel.mesh import make_mesh
from cpd_tpu.train import make_lm_train_step, make_optimizer
from cpd_tpu.train.state import TrainState
from flash_remat import compare_with_bare_remat

# a tiny cut of LFM2-24B-A2B's config.json: the reference's (published)
# keys; 4 of 8 experts held from id 2, heads of 8 in groups of two
CFG = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
           intermediate_size=48, moe_intermediate_size=24, num_experts=4,
           num_experts_published=8, expert_first=2, num_experts_per_tok=3,
           routed_scaling_factor=1.0, rope_parameters={"rope_theta": 1e6},
           norm_eps=1e-5, num_dense_layers=1, conv_L_cache=3,
           layer_types=["conv", "full_attention", "conv"], vocab_size=64)


def model_of(cfg=CFG, **kw):
    return conv_moe_lm(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=len(cfg["layer_types"]), layer_types=cfg["layer_types"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        first_dense=cfg["num_dense_layers"], l_cache=cfg["conv_L_cache"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        eps=cfg["norm_eps"], n_experts=cfg["num_experts_published"],
        experts_held=cfg["num_experts"], expert_first=cfg["expert_first"],
        top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        routed_scaling=cfg["routed_scaling_factor"],
        **{"init_std": 0.2, **kw})


def batch(seed=1, b=2, t=16, vocab=64):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (b, t + 1), 0, vocab)
    return toks[:, :-1], toks[:, 1:]


def params_of(seed=0, **kw):
    return model_of(**kw).init(jax.random.PRNGKey(seed),
                               batch()[0])["params"]


def mean_loss(model, a, b):
    def loss(p):
        logits = model.apply({"params": p}, a, mutable=[COUNTERS])[0]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b).mean()
    return loss


REF_LOSS = jax.jit(lambda p, a, b: ref.loss(p, a, b, CFG))
REF_GRAD = jax.jit(jax.value_and_grad(lambda p, a, b: ref.loss(p, a, b, CFG)))


def rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))


def norm_gap(a, b):
    return float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


# ---- the model against the plain reference ------------------------------

@pytest.mark.parametrize("impl,remat", [("xla", False), ("xla", True),
                                        ("flash", False), ("flash", True)])
def test_loss_and_every_gradient_leaf_match_reference(impl, remat):
    """float32 compute: the same arithmetic in another order, so loss and
    every leaf's gradient agree to float32 round-off (1e-5 of the leaf's
    largest element; 2.4e-6 is the most read, the interpreted flash
    kernels included).  The selection bias has no gradient on either
    side."""
    model = model_of(attn_impl=impl, remat=remat)
    a, b = batch()
    params = params_of()
    l1, g1 = jax.jit(jax.value_and_grad(mean_loss(model, a, b)))(params)
    l2, g2 = REF_GRAD(params, a, b)
    assert abs(float(l1) - float(l2)) < 1e-5 * float(l2)
    assert jax.tree.structure(g1) == jax.tree.structure(g2)
    assert max(jax.tree.leaves(jax.tree.map(rel, g1, g2))) < 1e-5
    for block in ("block1", "block2"):
        assert float(jnp.abs(g1[block]["moe"]["score_bias"]).max()) == 0.0
        assert float(jnp.abs(g2[block]["moe"]["score_bias"]).max()) == 0.0


@pytest.mark.parametrize("impl,blocks", [("flash", 1), ("xla", 0)])
def test_a_recomputed_block_runs_the_forward_kernel_once(impl, blocks,
                                                         monkeypatch):
    """One block of three has attention (`tests/flash_remat.py` says what
    is held against the bare `nn.remat`)."""
    a, b = batch()
    compare_with_bare_remat(
        monkeypatch, cm, mean_loss(model_of(attn_impl=impl, remat=True), a, b),
        params_of(), blocks)


def _routing_fixed(params):
    """The selection bias at 10 for experts 2, 3 and 4 in every routed
    layer: each token's three experts are those whatever its scores, so
    no rounding routes a token otherwise (the gates still come from the
    router's scores, which keep their gradient)."""
    bias = jnp.zeros((8,)).at[jnp.array([2, 3, 4])].set(10.0)
    return {name: ({**part, "moe": {**part["moe"], "score_bias": bias}}
                   if "moe" in part else part)
            for name, part in params.items()}


def test_model_in_bfloat16_on_the_flash_kernels_is_near_the_reference():
    """bf16 activations and products on the (interpreted) flash kernels,
    float32 norms, taps' sum and head: 8 bits of mantissa through 3
    layers move the loss by 6e-5 to 5.3e-4 of itself over five seeds
    (held to 0.2%) and a leaf's gradient by 0.03 to 0.071 of its norm
    (held to 10%: e5m2's own rounding is 5%).  With the routing free, a
    token whose 3rd and 4th scores lie within bf16's noise routes
    otherwise and the routed layers' leaves read 0.23 to 0.33: the
    routing is fixed here so that what is read is the arithmetic."""
    model = model_of(attn_impl="flash", remat=True, dtype=jnp.bfloat16)
    a, b = batch()
    params = _routing_fixed(params_of())
    l1, g1 = jax.jit(jax.value_and_grad(mean_loss(model, a, b)))(params)
    l2, g2 = REF_GRAD(params, a, b)
    assert abs(float(l1) - float(l2)) < 0.002 * float(l2)
    for block in ("block1", "block2"):      # no gradient: 0 / 0
        g1[block]["moe"].pop("score_bias")
        g2[block]["moe"].pop("score_bias")
    gaps = jax.tree.map(norm_gap, g1, g2)
    assert max(jax.tree.leaves(gaps)) < 0.10, gaps


# ---- the convolution ----------------------------------------------------

def test_short_conv_is_a_direct_loop_over_t():
    """Each output token t by hand: the gated products z_s of the three
    tokens s = t-2, t-1, t (none before the sequence's start), weighted by
    their taps, gated by C_t, projected."""
    conv = cm.ShortConv(3, init_std=0.3)
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 7, 8))
    params = conv.init(jax.random.PRNGKey(0), u)["params"]
    got = conv.apply({"params": params}, u)
    w_in, w_out = params["in_proj"]["kernel"], params["out_proj"]["kernel"]
    taps = params["taps"]
    for i in range(2):
        bcx = np.asarray(u[i] @ w_in)
        gb, gc, xt = bcx[:, :8], bcx[:, 8:16], bcx[:, 16:]
        for t in range(7):
            v = np.zeros(8, np.float32)
            for j in range(3):
                s = t - 2 + j
                if s >= 0:
                    v += np.asarray(taps[j]) * gb[s] * xt[s]
            np.testing.assert_allclose(got[i, t], (gc[t] * v) @ w_out,
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mixer", ["conv", "full_attention"])
def test_no_token_sees_a_later_one_or_another_sequence(mixer):
    """Changing token t+1 of the first sequence leaves every output up to
    t as it was; the second sequence does not move at all (the
    convolution's pad is each sequence's own)."""
    cfg = {**CFG, "layer_types": [mixer, mixer], "num_dense_layers": 2}
    model = model_of(cfg)
    a, _ = batch(t=12)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), a)["params"]
    apply = jax.jit(lambda x: model.apply({"params": params}, x))
    t = 6
    b = a.at[0, t + 1].set((a[0, t + 1] + 1) % 64)
    before, after = apply(a), apply(b)
    np.testing.assert_array_equal(before[0, :t + 1], after[0, :t + 1])
    assert float(jnp.abs(before[0, t + 1] - after[0, t + 1]).max()) > 0
    np.testing.assert_array_equal(before[1], after[1])
    # ... and the first token of the second sequence sees nothing of the
    # first sequence's last tokens
    c = a.at[0, -3:].set((a[0, -3:] + 5) % 64)
    np.testing.assert_array_equal(apply(c)[1],
                                  before[1])


# ---- the QK norms -------------------------------------------------------

def _attn(**kw):
    attn = cm.NormedGQA(4, 2, 8, rope_theta=1e6, init_std=0.3, **kw)
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 10, 32))
    pos = jnp.arange(10)
    params = attn.init(jax.random.PRNGKey(0), u, pos)["params"]
    return attn, params, u, pos


def test_the_qk_norms_scales_reach_the_scores_only():
    """The q norm's scale times c and the k norm's over c leave every
    output as it was (rotary is linear, so only their product reaches a
    score); a q scale of 0 makes every score 0, so each token's output is
    the mean of the values up to it, projected: nothing of the norms
    reaches v."""
    attn, params, u, pos = _attn()
    out = attn.apply({"params": params}, u, pos)
    scaled = {**params, "q_norm": {"scale": 4.0 * params["q_norm"]["scale"]},
              "k_norm": {"scale": params["k_norm"]["scale"] / 4.0}}
    np.testing.assert_allclose(attn.apply({"params": scaled}, u, pos), out,
                               rtol=1e-4, atol=1e-5)
    flat = {**params, "q_norm": {"scale": jnp.zeros((8,))}}
    v = (u @ params["v_proj"]["kernel"]).reshape(2, 10, 2, 1, 8)
    mean = jnp.cumsum(v, 1) / jnp.arange(1, 11)[None, :, None, None, None]
    want = jnp.broadcast_to(mean, (2, 10, 2, 2, 8)).reshape(2, 10, 32) @ (
        params["out_proj"]["kernel"])
    np.testing.assert_allclose(attn.apply({"params": flat}, u, pos), want,
                               rtol=1e-5, atol=1e-6)
    # one scale of the head's width each, shared by the heads
    assert params["q_norm"]["scale"].shape == (8,)
    assert params["k_norm"]["scale"].shape == (8,)


# ---- the layer pattern --------------------------------------------------

def test_an_unknown_layer_type_is_refused():
    model = model_of({**CFG, "layer_types": ["conv", "sliding_attention",
                                             "conv"]})
    with pytest.raises(ValueError, match="unknown mixer 'sliding_attention'"):
        model.init(jax.random.PRNGKey(0), batch()[0])
    with pytest.raises(ValueError, match="layer_types for"):
        conv_moe_lm(n_layers=3, layer_types=["conv", "conv"])


def test_parameter_tree_count_and_seeded_weights():
    from cpd_tpu.models.transformer import lm_param_specs
    from jax.sharding import PartitionSpec as P
    params = params_of(init_std=0.02)
    assert sorted(params) == ["block0", "block1", "block2", "embed",
                              "norm_f"]
    assert sorted(params["block0"]) == ["conv", "mlp", "norm1", "norm2"]
    assert sorted(params["block1"]) == ["attn", "moe", "norm1", "norm2"]
    assert sorted(params["block1"]["attn"]) == [
        "k_norm", "k_proj", "out_proj", "q_norm", "q_proj", "v_proj"]
    assert sorted(params["block2"]["conv"]) == ["in_proj", "out_proj",
                                                "taps"]
    specs = jax.tree.leaves(lm_param_specs(params),
                            is_leaf=lambda s: isinstance(s, P))
    assert specs and all(s == P() for s in specs)
    d, ff, hd, e, moe_ff, v = 32, 48, 8, 8, 24, 64
    conv = 4 * d * d + 3 * d
    attn = 2 * 4 * hd * d + 2 * 2 * hd * d + 2 * hd
    routed = d * e + e + 4 * 3 * d * moe_ff
    want = (conv + 3 * d * ff + 2 * d) + (attn + routed + 2 * d) + (
        conv + routed + 2 * d) + v * d + d
    assert sum(x.size for x in jax.tree.leaves(params)) == want
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        if "scale" in name or "score_bias" in name:
            assert bool((leaf == (1 if "scale" in name else 0)).all()), name
        elif leaf.size > 256:
            assert 0.015 < float(leaf.std()) < 0.025, name
    assert params["embed"]["embedding"].dtype == jnp.float32
    assert isinstance(get_model("conv_moe_lm", n_heads=8), cm.ConvMoELM)


def test_the_head_is_the_embedding():
    """Tied: the logits are float32, and a row of the embedding that no
    input token looks up still has a gradient, the head's (an untied
    head would leave it at 0)."""
    model = model_of()
    a, b = batch()
    a = a % 32                          # ids 32..63 are never looked up
    params = params_of()
    logits = jax.jit(model.apply)({"params": params}, a)
    assert logits.dtype == jnp.float32 and logits.shape == (2, 16, 64)
    grad = jax.jit(jax.grad(mean_loss(model, a, b)))(params)["embed"][
        "embedding"]
    assert float(jnp.abs(grad[32:]).min(-1).max()) > 0
    assert bool((jnp.abs(grad[32:]).max(-1) > 0).all())


# ---- the routed experts: shares of an expert-parallel group -------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """8 chips of an expert-parallel group, 2 of 16 experts each, top 4:
    the block's outputs of the 8 shares, with what every chip computes
    alike (the input and the mixer's residual, x + M(N1 x)) counted once,
    add up to the uncut reference block; every (token, slot) pair is held
    by one share."""
    n, held, k = 16, 2, 4
    cfg = {**CFG, "num_experts": n, "num_experts_published": n,
           "expert_first": 0, "num_experts_per_tok": k}
    block = lambda first, held: cm.ConvMoEBlock(
        "conv", True, 4, 2, 8, 3, 1e6, 48, n, held, first, k, 24, 1.0,
        init_std=0.2)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 12, 32))
    pos = jnp.arange(12)
    params = block(0, n).init(jax.random.PRNGKey(0), x, pos)["params"]
    want = jnp.stack([ref._block(x[i], params, "conv", True, cfg)
                      for i in range(2)])
    alike = jnp.stack([x[i] + ref._short_conv(mla_ref._rms_norm(
        x[i], params["norm1"]["scale"], 1e-5), params["conv"], cfg)
        for i in range(2)])
    total, pairs = -(n // held - 1) * alike, 0.0
    for first in range(0, n, held):
        share = {**params, "moe": {
            name: (w[first:first + held] if name.startswith("experts_")
                   else w) for name, w in params["moe"].items()}}
        out, sown = block(first, held).apply(
            {"params": share}, x, pos, mutable=[COUNTERS])
        total = total + out
        pairs += float(sown[COUNTERS]["moe"]["moe_pairs_held"][0])
    assert rel(total, want) < 1e-5
    assert pairs == 2 * 12 * k


# ---- through make_lm_train_step -----------------------------------------

def _sgd():
    return make_optimizer("sgd", lambda step: 0.01, momentum=0.9,
                          weight_decay=0.0)


def _state(model, tx, a):
    params = jax.jit(model.init)(jax.random.PRNGKey(0), a[:1, :8])["params"]
    return TrainState(step=jnp.zeros([], jnp.int32), params=params,
                      batch_stats={}, opt_state=tx.init(params))


@pytest.mark.parametrize("dp,impl", [(1, "xla"), (2, "flash")])
def test_step_with_e5m2_aps_reports_the_routing_counters(dp, impl):
    """The entry point the cell times, e5m2 APS, on 1 and 2 devices over
    `dp`: the loss is the reference's, the three routing counters are in
    the metrics and are the whole batch's, and the update is near the
    reference's SGD step (e5m2's rounding: 0.053 of an element)."""
    model = model_of(remat=True, attn_impl=impl)
    mesh = make_mesh(dp=dp, devices=jax.devices()[:dp])
    a, b = batch(b=4)
    state = _state(model_of(), _sgd(), a)
    step = make_lm_train_step(model, _sgd(), mesh, use_aps=True, grad_exp=5,
                              grad_man=2, mode="faithful", donate=False)
    new, metrics = step(state, a, b)
    want_loss, g = REF_GRAD(state.params, a, b)
    assert abs(float(metrics["loss"]) - float(want_loss)) < 1e-5
    _, sown = jax.jit(lambda p: model_of().apply(
        {"params": p}, a, mutable=[COUNTERS]))(state.params)
    by_layer = [float(blk["moe"]["moe_pairs_held"][0])
                for blk in sown[COUNTERS].values()]
    assert len(by_layer) == 2
    assert float(metrics["moe_pairs_held"]) == sum(by_layer) > 0
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0
    # 4 x 16 tokens, 3 slots, 4 of 8 experts held: under one row tile,
    # so no bound below the pairs and no `cond` (0)
    assert float(metrics["moe_compact"]) == 0.0
    moved = jax.tree.map(lambda n, o, gg: (n - o, -0.01 * gg), new.params,
                         state.params, g)
    pairs = jax.tree.leaves(moved, is_leaf=lambda x: isinstance(x, tuple))
    num = sum(float(jnp.sum((d - w) ** 2)) for d, w in pairs)
    den = sum(float(jnp.sum(w ** 2)) for _, w in pairs)
    assert (num / den) ** 0.5 < (0.08 if dp == 1 else 0.16)


def test_step_runs_over_the_row_bound_where_the_share_is_an_eighth():
    """The cell's share, 8 of 64 experts top-4, at a length whose 1,024
    pairs bound the rows by one tile of 512 (a quarter of them held by
    expectation): the routed layer runs over C rows, `moe_compact` 1,
    and the step's loss is the reference's."""
    cfg = {**CFG, "num_experts": 8, "num_experts_published": 64,
           "expert_first": 0, "num_experts_per_tok": 4,
           "layer_types": ["conv", "conv"]}
    model = model_of(cfg, remat=True)
    a, b = batch(b=2, t=128)
    state = _state(model, _sgd(), a)
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    _, metrics = make_lm_train_step(model, _sgd(), mesh, use_aps=True,
                                    grad_exp=5, grad_man=2,
                                    donate=False)(state, a, b)
    assert float(metrics["moe_compact"]) == 1.0
    assert abs(float(metrics["loss"]) - float(ref.loss(
        state.params, a, b, cfg))) < 1e-5


def test_scopes_of_the_step():
    """`cpd.conv_mixer` and `cpd.gqa_attn` under `cpd.loss_grad` in the
    compiled step's operation names (what a device trace carries), the
    flash kernels' under attention's, and the feed-forward parts under
    the scopes they had."""
    model = model_of(attn_impl="flash", remat=True, dtype=jnp.bfloat16)
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    a, b = batch()
    step = make_lm_train_step(model, _sgd(), mesh, donate=False)
    text = jax.jit(step).lower(_state(model, _sgd(), a), a,
                               b).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    for scope in (scopes.CONV_MIXER, scopes.GQA_ATTN, scopes.DENSE_MLP,
                  scopes.MOE_ROUTER, scopes.MOE_EXPERTS):
        assert any(scopes.LOSS_GRAD in n and scope in n for n in names), scope
    assert any(scopes.GQA_ATTN in n and scopes.KERNEL_FLASH_GQA_FWD in n
               for n in names)
    assert not any(scopes.CONV_MIXER in n and scopes.GQA_ATTN in n
                   for n in names)
