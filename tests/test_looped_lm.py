"""The looped LM (models/looped.py): one stack of layers run `n_loops`
times with shared weights, an exit gate and an exit-weighted loss that the
model owns and `train/lm.py` asks it for.

The oracle is the benchmark's plain float32 reference
(`benchmark/reference/looped_lm.py`), which shares no code with the
model.  CPU, tiny sizes, float32 compute unless a test says otherwise.
"""

from __future__ import annotations

import hashlib
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import looped_lm as ref
from cpd_tpu.models import get_model, looped_lm, transformer_lm
from cpd_tpu.models import looped as lm_module
from cpd_tpu.models.mla_moe import COUNTERS, RMSNorm
from cpd_tpu.parallel.mesh import make_mesh
from cpd_tpu.train import make_lm_train_step, make_optimizer
from cpd_tpu.train.state import TrainState
from flash_remat import compare_with_bare_remat

# a tiny cut of Ouro's config.json: the reference's (published) keys
CFG = dict(hidden_size=32, num_attention_heads=4, intermediate_size=48,
           num_hidden_layers=2, total_ut_steps=4, rope_theta=1000000,
           rms_norm_eps=1e-6, exit_entropy_beta=0.1, vocab_size=64)
GOLDEN = pathlib.Path(__file__).parent / "fixtures" / "lm_step_texts.json"


def model_of(cfg=CFG, **kw):
    return looped_lm(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], d_ff=cfg["intermediate_size"],
        n_loops=cfg["total_ut_steps"], rope_theta=float(cfg["rope_theta"]),
        eps=cfg["rms_norm_eps"], exit_beta=cfg["exit_entropy_beta"],
        **{"init_std": 0.2, **kw})


def batch(seed=1, b=2, t=16, vocab=64):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (b, t + 1), 0, vocab)
    return toks[:, :-1], toks[:, 1:]


def params_of(seed=0, cfg=CFG, **kw):
    return model_of(cfg, **kw).init(jax.random.PRNGKey(seed),
                                    batch()[0])["params"]


def token_losses(model, params, a, b):
    """((terms, hits), the counters the model sowed)."""
    (terms, hits), sown = model.apply({"params": params}, a, b,
                                      mutable=[COUNTERS],
                                      method="token_losses")
    return terms, hits, {k: v[0] for k, v in sown[COUNTERS].items()}


def mean_loss(model, a, b):
    return lambda p: token_losses(model, p, a, b)[0].mean()


REF_GRAD = jax.jit(jax.value_and_grad(lambda p, a, b: ref.loss(p, a, b, CFG)))


def rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))


def with_gate(params, kernel, bias):
    gate = {"kernel": jnp.full_like(params["exit_gate"]["kernel"], kernel),
            "bias": jnp.full_like(params["exit_gate"]["bias"], bias)}
    return {**params, "exit_gate": gate}


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


# ---- the model against the plain reference ------------------------------

@pytest.mark.parametrize("impl,remat", [("xla", False), ("flash", True),
                                        ("chunked", True)])
def test_loss_and_every_gradient_leaf_match_reference(impl, remat):
    """float32 compute: the same arithmetic in another order, so loss and
    every leaf's gradient agree to float32 round-off (5e-5 of the leaf's
    largest element, the interpreted flash kernels included; the most is
    the gate's one-element bias, a sum over every token and exit of terms
    that nearly cancel: 1.6e-5 on the chunked path)."""
    model = model_of(attn_impl=impl, remat=remat)
    a, b = batch()
    params = params_of()
    l1, g1 = jax.jit(jax.value_and_grad(mean_loss(model, a, b)))(params)
    l2, g2 = REF_GRAD(params, a, b)
    assert abs(float(l1) - float(l2)) < 1e-5 * float(l2)
    assert jax.tree.structure(g1) == jax.tree.structure(g2)
    assert max(jax.tree.leaves(jax.tree.map(rel, g1, g2))) < 5e-5


@pytest.mark.parametrize("impl,blocks", [("flash", 2), ("xla", 0)])
def test_a_recomputed_block_runs_the_forward_kernel_once(impl, blocks,
                                                         monkeypatch):
    """The pass's body holds both blocks once: the scan runs it four times
    (`tests/flash_remat.py` says what is held against the bare
    `nn.remat`)."""
    a, b = batch()
    compare_with_bare_remat(
        monkeypatch, lm_module,
        mean_loss(model_of(attn_impl=impl, remat=True), a, b), params_of(),
        blocks)


def test_model_in_bfloat16_on_the_flash_kernels_is_near_the_reference():
    """bf16 activations and products on the (interpreted) flash kernels:
    8 bits of mantissa through 2 layers x 4 passes of normed residuals
    move the loss by a few thousandths (held to 1%) and a leaf's gradient
    by 5-7 hundredths of its norm (held to 10%: e5m2's own rounding is
    5%, and a float32 result rounded ONCE to bf16 would read 0.2%).  The
    gate's one-element bias is a sum over every token and exit of terms
    that nearly cancel, reads 0.24 and is held to 0.5 (a sign kept)."""
    model = model_of(attn_impl="flash", remat=True, dtype=jnp.bfloat16)
    a, b = batch()
    params = params_of()
    l1, g1 = jax.jit(jax.value_and_grad(mean_loss(model, a, b)))(params)
    l2, g2 = REF_GRAD(params, a, b)
    assert abs(float(l1) - float(l2)) < 0.01 * float(l2)
    norm_gap = lambda x, y: float(jnp.linalg.norm((x - y).ravel())
                                  / jnp.linalg.norm(y.ravel()))
    gaps = jax.tree.map(norm_gap, g1, g2)
    assert gaps["exit_gate"].pop("bias") < 0.5
    assert max(jax.tree.leaves(gaps)) < 0.10, gaps


# ---- the loop against the same blocks written out R x L times -----------

def _unrolled_loss(model, copies, a, b):
    """The model's loss with the stack written out: pass r reads
    `copies[r]`, a whole parameter tree of its own (blocks, final norm,
    gate and head); nothing is scanned and nothing is shared."""
    block = lm_module.LoopBlock(model.n_heads, model.d_ff, model.rope_theta,
                                model.eps, model.attn_impl, model.dtype,
                                model.init_std)
    positions = jnp.arange(a.shape[1])
    h = copies[0]["embed"]["embedding"][a]
    ces, gates = [], []
    for p in copies:
        for i in range(model.n_layers):
            h = block.apply({"params": p[f"block{i}"]}, h, positions)
        h = RMSNorm(model.eps).apply({"params": p["final_norm"]}, h)
        ces.append(optax.softmax_cross_entropy_with_integer_labels(
            h @ p["lm_head"]["kernel"], b))
        gates.append((h @ p["exit_gate"]["kernel"])[..., 0]
                     + p["exit_gate"]["bias"][0])
    prob = ref.exit_probabilities(jnp.stack(gates))
    entropy = -jax.scipy.special.xlogy(prob, prob).sum(0)
    return ((prob * jnp.stack(ces)).sum(0)
            - model.exit_beta * entropy).mean()


def test_loop_is_the_unrolled_stack_with_tied_parameters():
    """Loss equal, and a shared weight's gradient is the sum of the
    gradients of its R untied copies (the embedding's is the first
    copy's: only the first pass reads it)."""
    model = model_of(remat=True)
    a, b = batch()
    params = params_of()
    l1, g1 = jax.value_and_grad(mean_loss(model, a, b))(params)
    copies = [params] * model.n_loops
    l2, g2 = jax.value_and_grad(
        lambda c: _unrolled_loss(model, c, a, b))(copies)
    assert abs(float(l1) - float(l2)) < 1e-6 * float(l2)
    summed = jax.tree.map(lambda *g: sum(g), *g2)
    assert max(jax.tree.leaves(jax.tree.map(rel, g1, summed))) < 1e-5
    # each pass's copy of a block does get a gradient of its own
    for g in g2:
        assert float(jnp.abs(g["block0"]["q_proj"]["kernel"]).max()) > 0
    assert all(float(jnp.abs(g["embed"]["embedding"]).max()) == 0
               for g in g2[1:])


def test_compiled_program_holds_the_stack_once():
    """One `while` over the passes: the lowered loss and gradient of four
    passes has as many matrix products as that of one pass."""
    a, b = batch()
    lowered = lambda cfg: jax.jit(jax.grad(mean_loss(model_of(cfg), a, b))
                                  ).lower(params_of()).as_text()
    four, one = lowered(CFG), lowered({**CFG, "total_ut_steps": 1})
    assert "stablehlo.while" in four
    assert four.count("stablehlo.dot_general") == one.count(
        "stablehlo.dot_general") > 0


# ---- the exit distribution and the loss ---------------------------------

def test_exit_probabilities_sum_to_one_in_every_token():
    gates = 3.0 * jax.random.normal(jax.random.PRNGKey(2), (4, 2, 16))
    log_p, p = lm_module.exit_distribution(gates)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(p, ref.exit_probabilities(
        gates.reshape(4, -1)).reshape(p.shape), atol=1e-6)
    np.testing.assert_allclose(jnp.exp(log_p), p)
    terms, _, _ = token_losses(model_of(), params_of(), *batch())
    assert terms.shape == (2, 16) and terms.dtype == jnp.float32


def test_gates_far_negative_leave_at_the_last_exit_only():
    """lam = 0 at every exit: p = (0, 0, 0, 1), the entropy term is 0 and
    the loss is the last exit's plain cross-entropy, finite, gradient
    included."""
    model = model_of()
    a, b = batch()
    params = with_gate(params_of(), 0.0, -1e4)
    _, p = lm_module.exit_distribution(jnp.full((4, 3), -1e4))
    np.testing.assert_array_equal(p, [[0.0] * 3] * 3 + [[1.0] * 3])
    terms, hits, counters = token_losses(model, params, a, b)
    logits = model.apply({"params": params}, a)
    want = optax.softmax_cross_entropy_with_integer_labels(logits, b)
    np.testing.assert_allclose(terms, want, rtol=1e-6)
    assert int(hits) == int(jnp.sum(jnp.argmax(logits, -1) == b))
    assert {k: float(v) for k, v in counters.items()} == {
        "loop_expected_exit": 4.0, "loop_last_exit_mass": 1.0}
    grads = jax.grad(mean_loss(model, a, b))(params)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))


def test_one_pass_is_a_plain_sandwich_norm_lm():
    """R = 1: one exit, p = 1, no entropy, whatever the gate says: the
    loss is the cross-entropy of `__call__`'s logits and the reference's."""
    cfg = {**CFG, "total_ut_steps": 1}
    model = model_of(cfg)
    a, b = batch()
    params = params_of(cfg=cfg)
    terms, _, counters = token_losses(model, params, a, b)
    want = optax.softmax_cross_entropy_with_integer_labels(
        model.apply({"params": params}, a), b)
    np.testing.assert_allclose(terms, want, rtol=1e-6)
    assert abs(float(terms.mean())
               - float(ref.loss(params, a, b, cfg))) < 1e-5
    assert {k: float(v) for k, v in counters.items()} == {
        "loop_expected_exit": 1.0, "loop_last_exit_mass": 1.0}
    gate = jax.grad(mean_loss(model, a, b))(params)["exit_gate"]
    assert float(jnp.abs(gate["kernel"]).max()) == 0.0


# ---- the model's shape --------------------------------------------------

def test_parameter_tree_and_seeded_weights():
    from cpd_tpu.models.transformer import lm_param_specs
    from jax.sharding import PartitionSpec as P
    params = params_of(init_std=0.02)
    assert sorted(params) == ["block0", "block1", "embed", "exit_gate",
                              "final_norm", "lm_head"]
    specs = jax.tree.leaves(lm_param_specs(params),
                            is_leaf=lambda s: isinstance(s, P))
    assert specs and all(s == P() for s in specs)
    d, ff, v = 32, 48, 64
    want = 2 * (4 * d * d + 3 * d * ff + 4 * d) + 2 * v * d + d + d + 1
    assert sum(x.size for x in jax.tree.leaves(params)) == want
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            assert bool((leaf == 1).all()), name
        elif name.endswith("['bias']"):
            assert bool((leaf == 0).all()), name
        elif leaf.size > 256:
            assert 0.015 < float(leaf.std()) < 0.025, name
    assert isinstance(get_model("looped_lm", n_heads=8), lm_module.LoopedLM)
    with pytest.raises(ValueError, match="n_kv_heads"):
        looped_lm(n_heads=8, n_kv_heads=2)


# ---- through make_lm_train_step -----------------------------------------

def _state(model, tx, toks, params=None):
    if params is None:
        params = model.init(jax.random.PRNGKey(0), toks)["params"]
    return TrainState(step=jnp.zeros([], jnp.int32), params=params,
                      batch_stats={}, opt_state=tx.init(params))


def _sgd():
    return make_optimizer("sgd", lambda step: 0.01, momentum=0.9,
                          weight_decay=0.0)


@pytest.mark.parametrize("impl,dtype,loss_tol,grad_tol", [
    # float32 on the plain path: round-off
    ("xla", jnp.float32, 1e-5, 1e-4),
    # bf16 on the interpreted flash kernels: 8 bits of mantissa through
    # 8 block applications (see the model test above)
    ("flash", jnp.bfloat16, 1e-2, 0.10)])
def test_step_loss_and_gradient_match_reference(impl, dtype, loss_tol,
                                                grad_tol):
    """The entry point the cell times, fp32 gradients: the step's loss is
    the reference's, and its gradient, read out of the momentum buffer
    after the first step, is the reference's leaf by leaf."""
    model = model_of(attn_impl=impl, remat=True, dtype=dtype)
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    a, b = batch(b=4)
    state = _state(model, _sgd(), a, params_of())
    step = make_lm_train_step(model, _sgd(), mesh, mode="fast",
                              donate=False)
    new, metrics = step(state, a, b)
    want_loss, want = REF_GRAD(state.params, a, b)
    assert abs(float(metrics["loss"]) - float(want_loss)) \
        < loss_tol * float(want_loss)
    assert 0.0 <= float(metrics["accuracy"]) <= 1.0
    norm_gap = lambda x, y: float(jnp.linalg.norm((x - y).ravel())
                                  / jnp.linalg.norm(y.ravel()))
    gaps = jax.tree.map(norm_gap, new.opt_state.momentum_buf, want)
    # the gate's one-element bias: see the model test above
    assert gaps["exit_gate"].pop("bias") < 5 * grad_tol
    assert max(jax.tree.leaves(gaps)) < grad_tol, gaps


@pytest.mark.parametrize("dp,emulate", [(1, 1), (2, 2)])
def test_step_counters_read_the_uniform_exit_at_zero_gate_weights(dp,
                                                                  emulate):
    """Every lam is 0.5 at zero gate weights: p = (1/2, 1/4, 1/8, 1/8),
    expected exit 1.875 and last-exit mass 0.125 in every token, so the
    `"mean"` merge over micro-batches and ranks reads the same; e5m2 APS
    through the same reduction as the other LM cells."""
    model = model_of(remat=True)
    mesh = make_mesh(dp=dp, devices=jax.devices()[:dp])
    a, b = batch(b=2 * dp * emulate)
    state = _state(model, _sgd(), a, with_gate(params_of(), 0.0, 0.0))
    step = make_lm_train_step(model, _sgd(), mesh, use_aps=True, grad_exp=5,
                              grad_man=2, mode="faithful", donate=False,
                              emulate_node=emulate)
    new, metrics = step(state, a, b)
    assert abs(float(metrics["loop_expected_exit"]) - 1.875) < 1e-6
    assert abs(float(metrics["loop_last_exit_mass"]) - 0.125) < 1e-6
    want_loss, g = REF_GRAD(state.params, a, b)
    assert abs(float(metrics["loss"]) - float(want_loss)) < 1e-5
    moved = jax.tree.map(lambda n, o, gg: (n - o, -0.01 * gg), new.params,
                         state.params, g)
    pairs = jax.tree.leaves(moved, is_leaf=lambda x: isinstance(x, tuple))
    num = sum(float(jnp.sum((d - w) ** 2)) for d, w in pairs)
    den = sum(float(jnp.sum(w ** 2)) for _, w in pairs)
    # e5m2's rounding: 0.053 of an element, summed over 2 x 2 terms
    assert (num / den) ** 0.5 < (0.08 if dp == 1 else 0.16)


def test_mean_counter_is_a_mean_over_micro_batches_and_ranks():
    """Seeded gates: the step's counter over 2 ranks x 2 micro-batches is
    the mean of the four parts' own readings."""
    model = model_of()
    mesh = make_mesh(dp=2, devices=jax.devices()[:2])
    a, b = batch(b=8)
    params = params_of()
    step = make_lm_train_step(model, _sgd(), mesh, mode="fast",
                              donate=False, emulate_node=2)
    _, metrics = step(_state(model, _sgd(), a, params), a, b)
    parts = [token_losses(model, params, a[i:i + 2], b[i:i + 2])[2]
             for i in range(0, 8, 2)]
    for name in ("loop_expected_exit", "loop_last_exit_mass"):
        want = sum(float(p[name]) for p in parts) / 4
        assert abs(float(metrics[name]) - want) < 1e-6
    assert 1.0 < float(metrics["loop_expected_exit"]) < 4.0


def test_label_smoothing_with_a_model_that_owns_its_loss_raises():
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="owns its loss"):
        make_lm_train_step(model_of(), _sgd(), mesh, label_smoothing=0.1)
    # ... and a model that does not own it is smoothed as before
    make_lm_train_step(transformer_lm(vocab_size=64, d_model=32, n_layers=1,
                                      n_heads=4), _sgd(), mesh,
                       label_smoothing=0.1)


def test_scopes_of_the_looped_step():
    """The three scopes and the kernels' under attention's, in the
    compiled step's operation names (what a device trace carries)."""
    import re

    from cpd_tpu.obs import scopes
    model = model_of(attn_impl="flash", remat=True, dtype=jnp.bfloat16)
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    a, b = batch()
    step = make_lm_train_step(model, _sgd(), mesh, donate=False)
    text = jax.jit(step).lower(_state(model, _sgd(), a), a,
                               b).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    for scope in (scopes.LOOP_ATTN, scopes.LOOP_MLP, scopes.LOOP_EXIT):
        assert any(scopes.LOSS_GRAD in n and scope in n for n in names)
    assert any(scopes.LOOP_ATTN in n and scopes.KERNEL_FLASH_GQA_FWD in n
               for n in names)
    assert {scopes.LOOP_ATTN, scopes.LOOP_MLP, scopes.LOOP_EXIT} <= {
        getattr(scopes, n) for n in scopes.__all__ if n.isupper()}


# ---- the other LM cells' steps are the parent's -------------------------

def _tiny_cells() -> dict:
    """The benchmark's three other LM cells at a tiny size: the tests'
    stand-ins for their configuration and traffic files."""
    from benchmark.tests import tiny
    # `benchmark/tests/test_mla_moe_cell.py`'s stand-in for Moonlight's files
    moe = {**tiny.LM_CONFIG, "runner": "train_mla_moe_lm",
           "model": "mla_moe_lm", "num_key_value_heads": 4,
           "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
           "v_head_dim": 16, "moe_intermediate_size": 32,
           "n_routed_experts": 4, "n_routed_experts_published": 8,
           "expert_first": 0, "num_experts_per_tok": 3,
           "n_shared_experts": 2, "routed_scaling_factor": 2.446,
           "rope_theta": 50000, "rms_norm_eps": 1e-5,
           "first_k_dense_replace": 1, "initializer_range": 0.02,
           "model_kwargs": {"attn_impl": "flash", "flash_bwd": "chunked",
                            "remat": True, "dtype": "bfloat16"}}
    fp32 = {**tiny.LM_TRAFFIC, "reduce": {"use_aps": False, "mode": "fast",
                                          "donate": True}}
    return {"starcoder2_3b_aps_e5m2_1chip": (tiny.LM_CONFIG, tiny.LM_TRAFFIC),
            "starcoder2_3b_fp32_1chip": (tiny.LM_CONFIG, fp32),
            "moonlight_16b_ep8_aps_e5m2_1chip": (moe, tiny.LM_TRAFFIC)}


def numbered_symbols(text: str) -> str:
    """`text` with each `@symbol` named by its base and its rank among the
    symbols of that base, in order of first appearance.  jax emits every
    distinct equation's lowering as a private function named after its
    primitive or function, and the module's symbol table gives a name
    already taken the suffix `_<n>` of one counter over the whole module:
    one more equation anywhere (a `checkpoint_name` is an identity that
    still takes a name) renumbers every later symbol."""
    ranks: dict = {}
    seen: dict = {}

    def rename(m):
        name = m.group(1)
        if name not in ranks:
            base = re.sub(r"_\d+$", "", name)
            ranks[name] = (base, seen.get(base, 0))
            seen[base] = ranks[name][1] + 1
        base, rank = ranks[name]
        return f"@{base}_{rank}" if rank else f"@{base}"
    return re.sub(r"@([\w.$-]+)", rename, text)


def lowered_step_sha1(config: dict, traffic: dict) -> str:
    import importlib
    # jax's tracing caches decide whether two calls of one function share
    # a lowering: entries that earlier work left behind (or evicted) can
    # split one private function in two, so the text is lowered from
    # empty caches
    jax.clear_caches()
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    runner = importlib.import_module(
        f"benchmark.runners.{config['runner']}").build(config, traffic, mesh,
                                                       None)
    key = jax.ShapeDtypeStruct((2,), "uint32")
    state = jax.eval_shape(runner.init_state, key)
    a, b = jax.eval_shape(runner.make_batch, key)
    with jax.default_matmul_precision(None):    # the cells set none
        text = jax.jit(runner.step).lower(state, a, b).as_text()
    return hashlib.sha1(numbered_symbols(text).encode()).hexdigest()


@pytest.mark.parametrize("cell", ["starcoder2_3b_aps_e5m2_1chip",
                                  "starcoder2_3b_fp32_1chip",
                                  "moonlight_16b_ep8_aps_e5m2_1chip"])
def test_other_lm_cells_steps_lower_to_the_parents_text(cell):
    """A model that does not own its loss goes the way it went: the
    lowered text of each other LM cell's step at a tiny size is, character
    for character, what the commit before the looped LM lowered
    (`tests/fixtures/lm_step_texts.json`: `lowered_step_sha1` of
    `_tiny_cells()` on a checkout of that commit; a PR that means to
    change those steps records them anew and says so).  PR 35 recorded
    the Moonlight entry anew: its stand-in holds 4 of 8 experts, so
    `RoutedExperts` has no row bound below its T·k pairs there and no
    `cond`, and its text differs from the parent's by the new counter
    alone (`moe_compact`: 18 scalar operations of its mean over layers,
    micro-batches and ranks, every other operation and type as it was);
    the step that does hold the `cond` is `test_mla_moe.py`'s, at 2 of
    8 experts held.  Character for character but for the numbers jax's
    symbol table appends to private functions (`numbered_symbols`): all
    three entries were recorded again with the symbols numbered, the
    StarCoder2 ones on the commit before recomputed blocks kept the flash
    kernel's results (the two names that keeps add to every program that
    calls the kernel moved each later symbol's number by one and nothing
    else), the Moonlight one on the commit after it, whose blocks
    recompute everything but the forward kernel."""
    golden = json.loads(GOLDEN.read_text())
    if golden["jax"] != jax.__version__:
        pytest.skip(f"texts recorded under jax {golden['jax']}")
    assert lowered_step_sha1(*_tiny_cells()[cell]) == golden["sha1"][cell]
