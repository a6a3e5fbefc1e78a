"""The gradient stage (cpd_tpu.train.grads): written once, reached by
every step builder.

Bitwise equality of what it computes is held where it always was
(tests/test_overlap.py, test_zero.py, test_sr_pipeline.py, test_moe.py,
test_pipeline.py); this file holds the shape of the thing: each of the
four builders reaches the stage's one `sum_gradients` call exactly once
when its step is traced, and spells none of the stage's steps itself.
"""

import ast
import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import pytest

import cpd_tpu.train.grads as grads
from cpd_tpu.parallel.mesh import data_parallel_mesh, make_mesh
from cpd_tpu.train import create_train_state, make_optimizer
from cpd_tpu.train.state import TrainState

TRAIN = pathlib.Path(grads.__file__).parent
# what the stage owns: no builder calls or imports these
STAGE_OWNS = {"sum_gradients", "grad_sr_key", "overlapped_grads",
              "emulate_node_reduce", "make_overlap_emulate_fn",
              "sat_pressure_factor", "BucketPlan", "extract_bucket_shards"}
SR = dict(use_aps=True, grad_exp=5, grad_man=2, grad_rounding="stochastic",
          grad_seed=3, donate=False)


def _tx():
    return make_optimizer("sgd", lambda s: jnp.float32(0.1))


def _lm_state(model, tx, t):
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, t), jnp.int32)))["params"]
    return jax.eval_shape(lambda p: TrainState(
        step=jnp.zeros([], jnp.int32), params=p, batch_stats={},
        opt_state=tx.init(p)), params)


def _vision():
    from cpd_tpu.models.tiny import tiny_cnn
    from cpd_tpu.train import make_train_step
    model, tx = tiny_cnn(num_classes=4, width=4), _tx()
    state = jax.eval_shape(lambda: create_train_state(
        model, tx, jnp.zeros((2, 8, 8, 3)), jax.random.PRNGKey(0)))
    step = make_train_step(model, tx, data_parallel_mesh(), **SR)
    return step, (state, jax.ShapeDtypeStruct((16, 8, 8, 3), jnp.float32),
                  jax.ShapeDtypeStruct((16,), jnp.int32))


def _lm():
    from cpd_tpu.models.transformer import transformer_lm
    from cpd_tpu.train import make_lm_train_step
    kw = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4)
    model = transformer_lm(tp_axis="tp", sp_axis="sp", tp_size=2, **kw)
    tx = _tx()
    toks = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    step = make_lm_train_step(model, tx, make_mesh(dp=2, sp=2, tp=2), **SR)
    return step, (_lm_state(transformer_lm(**kw), tx, 16), toks, toks)


def _moe():
    from cpd_tpu.models.moe import moe_lm
    from cpd_tpu.train.moe import make_moe_train_step
    kw = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
              n_experts=4, capacity_factor=8.0)
    tx = _tx()
    toks = jax.ShapeDtypeStruct((16, 8), jnp.int32)
    step = make_moe_train_step(moe_lm(ep_axis="ep", ep_size=2, **kw), tx,
                               make_mesh(dp=4, ep=2), **SR)
    return step, (_lm_state(moe_lm(**kw), tx, 8), toks, toks)


def _pp():
    from cpd_tpu.models.pipeline_lm import pipelined_lm
    from cpd_tpu.train.pp import make_pp_train_step
    kw = dict(vocab_size=64, d_model=32, n_layers=4, n_heads=4, d_ff=64)
    tx = _tx()
    toks = jax.ShapeDtypeStruct((16, 16), jnp.int32)
    step = make_pp_train_step(pipelined_lm(pp_axis="pp", pp_size=2, **kw),
                              tx, make_mesh(pp=2, dp=4), n_microbatches=4,
                              **SR)
    return step, (_lm_state(pipelined_lm(**kw), tx, 16), toks, toks)


@pytest.mark.parametrize("module,build", [
    ("step", _vision), ("lm", _lm), ("moe", _moe), ("pp", _pp)])
def test_builder_reaches_the_stage_once_and_spells_none_of_it(
        module, build, monkeypatch):
    calls = []
    real = grads.sum_gradients

    def spy(local, axis_name, **kw):
        calls.append((axis_name, kw["rounding"], kw["key"] is not None))
        return real(local, axis_name, **kw)

    monkeypatch.setattr(grads, "sum_gradients", spy)
    step, args = build()
    jax.eval_shape(step, *args)
    assert calls == [("dp", "stochastic", True)]

    tree = ast.parse((TRAIN / f"{module}.py").read_text())
    named = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
    named |= {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)}
    named |= {a.name for n in ast.walk(tree)
              if isinstance(n, (ast.Import, ast.ImportFrom))
              for a in n.names}
    assert not named & STAGE_OWNS, sorted(named & STAGE_OWNS)


def test_options_derive_every_keyword_set_from_one_record():
    opts = grads.ReduceOptions(use_aps=True, grad_exp=4, grad_man=3,
                               mode="ring", grad_rounding="stochastic",
                               bucket_elems=100, block_scale=True,
                               block_size=32).check()
    wire = opts.wire_kw()
    assert wire == dict(use_aps=True, grad_exp=4, grad_man=3,
                        use_kahan=False, mode="ring", rounding="stochastic",
                        block_scale=True, block_size=32)
    assert opts.reduce_kw() == dict(wire, bucket_elems=100)
    with pytest.raises(dataclasses.FrozenInstanceError):
        opts.mode = "fast"


@pytest.mark.parametrize("bad,match", [
    (dict(grad_rounding="up"), "grad_rounding"),
    (dict(block_scale=True, mode="faithful"), "mode='ring'")])
def test_options_refuse_at_build_time(bad, match):
    with pytest.raises(ValueError, match=match):
        grads.ReduceOptions(**bad).check()


def test_block_scale_off_the_ring_is_the_updaters_to_carry():
    # an updater that owns the collective (ZeRO-2) carries the blocked
    # wire on its all_to_all: nothing for the stage to refuse
    grads.ReduceOptions(block_scale=True).check(reduce=False)


def test_report_metrics_names_only_what_was_asked():
    report = dict(ok=jnp.int32(1), hop_bad=jnp.int32(0),
                  gather_bad=jnp.int32(0), agree=jnp.int32(1),
                  wire_sat=jnp.float32(2), wire_underflow=jnp.float32(0),
                  wire_nan=jnp.float32(0), wire_total=jnp.float32(9),
                  aps_bad=jnp.int32(0))
    v = grads.report_metrics(report, grads.ReduceOptions(verify_reduce=True))
    assert sorted(v) == ["reduce_agree", "reduce_gather_bad",
                         "reduce_hop_bad", "reduce_ok"]
    q = grads.report_metrics(report, grads.ReduceOptions(quant_stats=True))
    assert sorted(q) == ["prec_aps_bad", "prec_wire_nan", "prec_wire_sat",
                         "prec_wire_total", "prec_wire_underflow"]
    assert all(x.dtype == jnp.float32 for x in {**v, **q}.values())
    assert float(q["prec_wire_total"]) == 9.0
    assert grads.report_metrics(None, grads.ReduceOptions()) == {}
