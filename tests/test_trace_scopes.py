"""The step's layers are named inside the compiled program (obs/scopes.py).

What a device trace shows of an operation is the `op_name=` metadata of
its HLO instruction (a TPU trace's `tf_op`): jax's name stack.  These
tests read that metadata off the compiled step, on the CPU mesh, for each
step builder x reduction mode x APS on/off, and hold the scopes to three
promises: every layer a configuration runs is named, nothing is named
that `obs/scopes.py` does not know, and a scope costs no operation.
"""

import ast
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cpd_tpu.obs import scopes
from cpd_tpu.parallel.mesh import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPONENT = re.compile(r"^(cpd|aps|wire|reduce|kernel)\.")
KNOWN = {getattr(scopes, n) for n in scopes.__all__
         if isinstance(getattr(scopes, n), str)} - {scopes.KERNEL_PREFIX}
KNOWN |= set(scopes.KERNELS)
TOP = {scopes.LOSS_GRAD, scopes.EMULATE_NODE, scopes.REDUCE,
       scopes.OPTIMIZER, scopes.METRICS}
APS = {scopes.APS_MAX_EXP, scopes.APS_SCALE, scopes.APS_UNSCALE}
# the refinements `sum_gradients` should show under `cpd.reduce`
UNDER_REDUCE = {
    ("faithful", True): APS | {scopes.WIRE_CAST, scopes.WIRE_PACK,
                               scopes.WIRE_UNPACK, scopes.WIRE_COLLECTIVE,
                               scopes.REDUCE_SCAN},
    ("faithful", False): {scopes.WIRE_COLLECTIVE, scopes.REDUCE_SCAN},
    ("fast", True): APS | {scopes.WIRE_CAST, scopes.WIRE_COLLECTIVE},
    ("fast", False): {scopes.WIRE_CAST, scopes.WIRE_COLLECTIVE},
    ("ring", True): APS | {scopes.WIRE_CAST, scopes.WIRE_PACK,
                           scopes.WIRE_UNPACK, scopes.WIRE_COLLECTIVE},
    ("ring", False): {scopes.WIRE_PACK, scopes.WIRE_UNPACK,
                      scopes.WIRE_COLLECTIVE},
}
SEEN: set = set()      # every component some compiled program carried


def innermost(component: str) -> str:
    """jax wraps a scope entered under a transformation in the
    transformation's marker: `transpose(jvp(cpd.reduce))` -> `cpd.reduce`."""
    return component.rsplit("(", 1)[-1].rstrip(")")


def scope_paths(compiled) -> set:
    """The scope components of every instruction's `op_name`, in order."""
    names = re.findall(r'op_name="([^"]+)"', compiled.as_text())
    assert names, "the compiled program carries no op_name metadata"
    return {tuple(innermost(c) for c in n.split("/")
                  if COMPONENT.match(innermost(c)))
            for n in names} - {()}


def _vision(mode, use_aps, dp=4):
    from cpd_tpu.models.tiny import tiny_cnn
    from cpd_tpu.train import (create_train_state, make_optimizer,
                               make_train_step)
    mesh = make_mesh(dp=dp, devices=jax.devices()[:dp])
    model = tiny_cnn(num_classes=4, width=4)
    tx = make_optimizer("sgd", lambda step: 0.1, momentum=0.9)
    state = create_train_state(model, tx, jnp.zeros((2, 8, 8, 3)),
                               jax.random.PRNGKey(0))
    step = make_train_step(model, tx, mesh, use_aps=use_aps, grad_exp=5,
                           grad_man=2, mode=mode, emulate_node=2,
                           donate=False)
    x = jnp.zeros((16, 8, 8, 3), jnp.float32)
    y = jnp.asarray(np.arange(16) % 4, jnp.int32)
    return step, (state, x, y)


def _lm(mode, use_aps):
    from cpd_tpu.models import transformer_lm
    from cpd_tpu.train import (create_train_state, make_lm_train_step,
                               make_optimizer)
    mesh = make_mesh(dp=4, devices=jax.devices()[:4])
    model = transformer_lm(vocab_size=64, d_model=32, n_layers=1, n_heads=4,
                           n_kv_heads=2, d_ff=64, attn_impl="flash")
    init_model = transformer_lm(vocab_size=64, d_model=32, n_layers=1,
                                n_heads=4, n_kv_heads=2, d_ff=64)
    tx = make_optimizer("sgd", lambda step: 0.01, momentum=0.9)
    state = create_train_state(init_model, tx, jnp.zeros((1, 16), jnp.int32),
                               jax.random.PRNGKey(0))
    step = make_lm_train_step(model, tx, mesh, use_aps=use_aps, grad_exp=5,
                              grad_man=2, mode=mode, emulate_node=2,
                              donate=False)
    toks = jnp.zeros((8, 128), jnp.int32)
    # the LM stepper is a plain function over a cache of jitted programs
    return jax.jit(step), (state, toks, toks)


@pytest.mark.parametrize("use_aps", [True, False], ids=["aps", "noaps"])
@pytest.mark.parametrize("mode", ["faithful", "fast", "ring"])
@pytest.mark.parametrize("builder", [_vision, _lm], ids=["vision", "lm"])
def test_compiled_step_names_its_layers(builder, mode, use_aps):
    step, args = builder(mode, use_aps)
    paths = scope_paths(step.lower(*args).compile())
    found = {c for p in paths for c in p}
    SEEN.update(found)
    assert found <= KNOWN, f"unknown scope names: {sorted(found - KNOWN)}"
    assert TOP <= found, f"missing: {sorted(TOP - found)}"
    # a refinement belongs to the reduction or the node emulation, never
    # to the bare step
    for p in paths:
        if p[0] not in TOP and not p[0].startswith(scopes.KERNEL_PREFIX):
            # a nested jit's body keeps only its own part of the stack
            assert p[0] in (scopes.WIRE_PACK, scopes.WIRE_UNPACK), p
    under = {c for p in paths if scopes.REDUCE in p
             for c in p[p.index(scopes.REDUCE) + 1:]}
    assert under == UNDER_REDUCE[mode, use_aps], (
        sorted(under ^ UNDER_REDUCE[mode, use_aps]))
    if builder is _lm:
        assert {scopes.KERNEL_FLASH_GQA_FWD, scopes.KERNEL_FLASH_GQA_BWD_DQ,
                scopes.KERNEL_FLASH_GQA_BWD_DKV} <= found


def test_one_rank_reduction_is_local_and_has_no_wire():
    """Over an axis of one rank the faithful reduction runs under
    `reduce.local`: nothing is packed, unpacked or gathered, so a
    one-chip trace reads nothing under those names."""
    step, args = _vision("faithful", True, dp=1)
    paths = scope_paths(step.lower(*args).compile())
    found = {c for p in paths for c in p}
    SEEN.update(found)
    assert found <= KNOWN and TOP <= found
    under = {c for p in paths if scopes.REDUCE in p
             for c in p[p.index(scopes.REDUCE) + 1:]}
    assert under == APS | {scopes.WIRE_CAST, scopes.REDUCE_LOCAL,
                           scopes.REDUCE_SCAN}, sorted(under)
    assert all(p[p.index(scopes.REDUCE_SCAN) - 1] == scopes.REDUCE_LOCAL
               for p in paths
               if scopes.REDUCE in p and scopes.REDUCE_SCAN in p)


def test_backward_is_marked_after_the_loss_grad_scope():
    step, args = _vision("fast", False)
    names = re.findall(r'op_name="([^"]+)"',
                       step.lower(*args).compile().as_text())
    tails = [n.split(scopes.LOSS_GRAD, 1)[1] for n in names
             if scopes.LOSS_GRAD in n]
    assert any("transpose(" in t for t in tails)
    assert any("jvp(" in t and "transpose(" not in t for t in tails)


def test_overlapped_reduction_is_the_reductions_not_the_backwards():
    """`overlap_reduce` runs the reduction inside the backward pass: its
    operations read `cpd.loss_grad/transpose(...)/cpd.reduce/...`, and the
    LAST `cpd.*` component is the owner."""
    from cpd_tpu.models.tiny import tiny_cnn
    from cpd_tpu.train import (create_train_state, make_optimizer,
                               make_train_step)
    mesh = make_mesh(dp=4, devices=jax.devices()[:4])
    model = tiny_cnn(num_classes=4, width=4)
    tx = make_optimizer("sgd", lambda step: 0.1, momentum=0.9)
    state = create_train_state(model, tx, jnp.zeros((2, 8, 8, 3)),
                               jax.random.PRNGKey(0))
    step = make_train_step(model, tx, mesh, use_aps=True, grad_exp=5,
                           grad_man=2, mode="ring", overlap_reduce=True,
                           emulate_node=2, bucket_elems=100, donate=False)
    paths = scope_paths(step.lower(
        state, jnp.zeros((16, 8, 8, 3)),
        jnp.zeros((16,), jnp.int32)).compile())
    SEEN.update(c for p in paths for c in p)
    nested = [p for p in paths
              if p[0] == scopes.LOSS_GRAD and scopes.REDUCE in p]
    assert nested, sorted(paths)
    assert any(p[0] == scopes.LOSS_GRAD and scopes.EMULATE_NODE in p
               for p in paths)


MODEL_LAYERS = {scopes.MLA, scopes.MOE_ROUTER, scopes.MOE_DISPATCH,
                scopes.MOE_EXPERTS, scopes.MOE_COMBINE, scopes.MOE_SHARED,
                scopes.DENSE_MLP}


def test_model_layers_are_named_under_the_loss_grad_scope():
    """`models/mla_moe.py` names its layers; through the LM step each is
    nested under `cpd.loss_grad` (so the step's forward and backward
    metrics count them), the flash kernel's scope under `cpd.mla`."""
    from cpd_tpu.models import mla_moe_lm
    from cpd_tpu.train import make_lm_train_step, make_optimizer
    from cpd_tpu.train.state import TrainState
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    kw = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=48,
              kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=6,
              n_experts=8, experts_held=2, top_k=3, moe_d_ff=24,
              n_shared_experts=2, remat=True)
    model = mla_moe_lm(**kw, attn_impl="flash")
    tx = make_optimizer("sgd", lambda step: 0.01, momentum=0.9)
    toks = jnp.zeros((2, 16), jnp.int32)
    params = mla_moe_lm(**kw).init(jax.random.PRNGKey(0), toks)["params"]
    state = TrainState(step=jnp.zeros([], jnp.int32), params=params,
                       batch_stats={}, opt_state=tx.init(params))
    step = jax.jit(make_lm_train_step(model, tx, mesh, use_aps=True,
                                      grad_exp=5, grad_man=2, donate=False))
    paths = scope_paths(step.lower(state, toks, toks).compile())
    found = {c for p in paths for c in p}
    SEEN.update(found)
    assert found <= KNOWN and MODEL_LAYERS <= found
    for layer in MODEL_LAYERS:
        assert (scopes.LOSS_GRAD, layer) in {p[:2] for p in paths}, layer
    for kernel in (scopes.KERNEL_FLASH_GQA_FWD, scopes.KERNEL_FLASH_GQA_BWD_DQ,
                   scopes.KERNEL_FLASH_GQA_BWD_DKV):
        assert (scopes.LOSS_GRAD, scopes.MLA, kernel) in paths, kernel


def test_looped_model_layers_are_named_under_the_loss_grad_scope():
    """`models/looped.py` names a block's attention, its MLP and a pass's
    exit; through the LM step each is nested under `cpd.loss_grad`, in
    every pass of the loop under the same three, the flash kernels'
    scopes under `cpd.loop_attn`."""
    from cpd_tpu.models import looped_lm
    from cpd_tpu.train import make_lm_train_step, make_optimizer
    from cpd_tpu.train.state import TrainState
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    kw = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=48,
              n_loops=3, remat=True)
    model = looped_lm(**kw, attn_impl="flash")
    tx = make_optimizer("sgd", lambda step: 0.01, momentum=0.9)
    toks = jnp.zeros((2, 16), jnp.int32)
    params = looped_lm(**kw).init(jax.random.PRNGKey(0), toks)["params"]
    state = TrainState(step=jnp.zeros([], jnp.int32), params=params,
                       batch_stats={}, opt_state=tx.init(params))
    step = jax.jit(make_lm_train_step(model, tx, mesh, use_aps=True,
                                      grad_exp=5, grad_man=2, donate=False))
    paths = scope_paths(step.lower(state, toks, toks).compile())
    found = {c for p in paths for c in p}
    SEEN.update(found)
    layers = {scopes.LOOP_ATTN, scopes.LOOP_MLP, scopes.LOOP_EXIT}
    assert found <= KNOWN and layers <= found
    for layer in layers:
        assert (scopes.LOSS_GRAD, layer) in {p[:2] for p in paths}, layer
    for kernel in (scopes.KERNEL_FLASH_GQA_FWD, scopes.KERNEL_FLASH_GQA_BWD_DQ,
                   scopes.KERNEL_FLASH_GQA_BWD_DKV):
        assert (scopes.LOSS_GRAD, scopes.LOOP_ATTN, kernel) in paths, kernel


def test_conv_attention_hybrid_layers_are_named_under_the_loss_grad_scope():
    """`models/conv_moe.py` names a conv layer's mixer and an attention
    layer's; through the LM step each is nested under `cpd.loss_grad`, the
    flash kernels' scopes under `cpd.gqa_attn`, and the feed-forward parts
    keep the scopes of `models/mla_moe.py`."""
    from cpd_tpu.models import conv_moe_lm
    from cpd_tpu.train import make_lm_train_step, make_optimizer
    from cpd_tpu.train.state import TrainState
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    kw = dict(vocab_size=64, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2,
              d_ff=48, layer_types=("conv", "full_attention", "conv"),
              n_experts=8, experts_held=4, top_k=2, moe_d_ff=24, remat=True)
    model = conv_moe_lm(**kw, attn_impl="flash")
    tx = make_optimizer("sgd", lambda step: 0.01, momentum=0.9)
    toks = jnp.zeros((2, 16), jnp.int32)
    params = conv_moe_lm(**kw).init(jax.random.PRNGKey(0), toks)["params"]
    state = TrainState(step=jnp.zeros([], jnp.int32), params=params,
                       batch_stats={}, opt_state=tx.init(params))
    step = jax.jit(make_lm_train_step(model, tx, mesh, use_aps=True,
                                      grad_exp=5, grad_man=2, donate=False))
    paths = scope_paths(step.lower(state, toks, toks).compile())
    found = {c for p in paths for c in p}
    SEEN.update(found)
    layers = {scopes.CONV_MIXER, scopes.GQA_ATTN, scopes.DENSE_MLP,
              scopes.MOE_ROUTER, scopes.MOE_EXPERTS}
    assert found <= KNOWN and layers <= found
    for layer in layers:
        assert (scopes.LOSS_GRAD, layer) in {p[:2] for p in paths}, layer
    for kernel in (scopes.KERNEL_FLASH_GQA_FWD, scopes.KERNEL_FLASH_GQA_BWD_DQ,
                   scopes.KERNEL_FLASH_GQA_BWD_DKV):
        assert (scopes.LOSS_GRAD, scopes.GQA_ATTN, kernel) in paths, kernel


def test_every_scope_is_used_and_lives_in_one_place():
    """Runs after the parametrised cases (same file, same worker): every
    non-kernel constant showed up in some compiled program; every kernel
    constant is used by a `pallas_call` site; and no scope string is
    spelled out anywhere else in the package."""
    if not SEEN:
        pytest.skip("needs the parametrised cases of this file")
    assert KNOWN - set(scopes.KERNELS) <= SEEN, sorted(
        KNOWN - set(scopes.KERNELS) - SEEN)
    by_value = {getattr(scopes, n): n for n in dir(scopes)
                if n.startswith("KERNEL_") and n != "KERNEL_PREFIX"}
    assert set(by_value) == set(scopes.KERNELS)
    uses = {n: 0 for n in by_value.values()}
    literals = []
    for base, _, files in os.walk(os.path.join(ROOT, "cpd_tpu")):
        for f in files:
            path = os.path.join(base, f)
            if not f.endswith(".py") or path.endswith("obs/scopes.py"):
                continue
            with open(path) as fh:
                tree = ast.parse(fh.read())
            for node in ast.walk(tree):
                if (isinstance(node, ast.Attribute) and node.attr in uses
                        and isinstance(node.value, ast.Name)
                        and node.value.id == "scopes"):
                    uses[node.attr] += 1
                if (isinstance(node, ast.Constant)
                        and isinstance(node.value, str)
                        and (node.value in KNOWN or node.value.startswith(
                            scopes.KERNEL_PREFIX))):
                    literals.append((os.path.relpath(path, ROOT),
                                     node.lineno, node.value))
    # each kernel constant names its `pallas_call` and scopes it
    assert all(n == 2 for n in uses.values()), uses
    assert not literals, literals


def _eqns(jaxpr):
    """Every equation, sub-programs included, in order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _prims(jaxpr) -> list:
    return [e.primitive.name for e in _eqns(jaxpr)
            if e.primitive.name not in ("pjit", "jit")]


def _unscoped(fn):
    """The function under its `jax.named_scope` decorator (and `jax.jit`,
    where it has one)."""
    while hasattr(fn, "__wrapped__"):
        fn = fn.__wrapped__
    return fn


def _scoped_functions():
    from cpd_tpu.parallel import aps, emulate, reduction
    from cpd_tpu.quant import numerics
    g = jnp.linspace(-3.0, 3.0, 24, dtype=jnp.float32).reshape(2, 12)
    tree = {"a": g, "b": g[0]}
    shifts = jnp.asarray([2.0, -1.0], jnp.float32)
    q = numerics.cast_to_format(g, 5, 2)
    return [
        (aps.aps_max_exponents, (tree, 4.0)),
        (aps.aps_scale, (tree, shifts)),
        (aps.aps_unscale, (tree, shifts)),
        (reduction.ordered_quantized_sum, (g, 5, 2)),
        (reduction.kahan_quantized_sum, (g, 5, 2)),
        (emulate.emulate_node_reduce, ({"a": g}, 2, True, 5, 2)),
        (numerics.pack_exmy, (q, 5, 2)),
        (numerics.unpack_exmy, (numerics.pack_exmy(q, 5, 2), 5, 2)),
    ]


@pytest.mark.parametrize("index", range(8))
def test_a_scope_costs_no_operation(index):
    """A scoped function traces to the equations of the function under
    the scope, and computes the same bits."""
    fn, args = _scoped_functions()[index]
    bare = _unscoped(fn)
    assert bare is not fn
    static = tuple(i for i, a in enumerate(args)
                   if isinstance(a, (int, bool, float)))
    with_scope = jax.make_jaxpr(fn, static_argnums=static)(*args)
    without = jax.make_jaxpr(bare, static_argnums=static)(*args)
    assert _prims(with_scope.jaxpr) == _prims(without.jaxpr)

    def named(jaxpr):
        return {c for e in _eqns(jaxpr)
                for c in str(e.source_info.name_stack).split("/")
                if COMPONENT.match(c)}

    # the function's own scope is the one thing the decorator adds
    assert len(named(with_scope.jaxpr) - named(without.jaxpr)) == 1
    for a, b in zip(jax.tree.leaves(fn(*args)),
                    jax.tree.leaves(bare(*args))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kernel_scope_and_name_agree():
    """A Pallas kernel is findable twice over: by its scope and by the
    `name=` of its `pallas_call`."""
    from cpd_tpu.ops.quantize import quantize_pallas
    x = jnp.linspace(-2.0, 2.0, 1024, dtype=jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda v: quantize_pallas(v, 5, 2, interpret=True))(x)
    calls = [e for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    stack = str(calls[0].source_info.name_stack).split("/")
    assert scopes.KERNEL_QUANTIZE in stack
    assert calls[0].params["name"] == scopes.kernel_name(
        scopes.KERNEL_QUANTIZE)
    with pytest.raises(ValueError):
        scopes.kernel_name(scopes.REDUCE)
