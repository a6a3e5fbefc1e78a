"""Overlapped backward-reduce (cpd_tpu.parallel.overlap) — ISSUE 8.

The load-bearing property is BITWISE invariance: the bucketed,
dependency-scheduled transport must produce exactly the bits of the
post-backward monolith — per-leaf vs bucketed vs overlapped for the
faithful path (any layout), overlap on/off at a FIXED bucket layout for
the ring, across formats, world sizes, Kahan and SR.  On top of that:
the structural overlap evidence (collectives interleaved with backward
compute in the emitted program), report parity for verify/stats through
the tap-cotangent channel, and the FaultPlan wire/sat attacks still
firing (with exact counters) under the new schedule.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from cpd_tpu.compat import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from cpd_tpu.parallel.mesh import data_parallel_mesh, make_mesh
from cpd_tpu.parallel.overlap import (BucketPlan, REPORT_FIELDS,
                                      bucket_layout, overlap_evidence,
                                      overlapped_grads)

W = 8  # conftest forces 8 virtual devices
_KEY = jax.random.PRNGKey(17)


def _bitwise(a, b, msg=""):
    np.testing.assert_array_equal(np.asarray(a).view(np.uint32),
                                  np.asarray(b).view(np.uint32),
                                  err_msg=msg)


def _tree(world, seed=0):
    rng = np.random.RandomState(seed)
    return {"a": (rng.randn(world, 37) * 0.2).astype(np.float32),
            "b": (rng.randn(world, 53) * 0.2).astype(np.float32),
            "c": (rng.randn(world, 11) * 0.2).astype(np.float32)}


def _shard(mesh, tree):
    return jax.tree.map(
        lambda g: jax.device_put(jnp.asarray(g),
                                 NamedSharding(mesh, P("dp"))), tree)


# ------------------------------------------------ bucket layout

def test_bucket_layout_greedy_capping():
    assert bucket_layout([10, 10, 10], 20) == [[0, 1], [2]]
    assert bucket_layout([10, 10, 10], 30) == [[0, 1, 2]]
    assert bucket_layout([10, 10, 10], 10) == [[0], [1], [2]]
    # an oversized leaf forms its own bucket (never split)
    assert bucket_layout([100, 5, 5], 20) == [[0], [1, 2]]
    assert bucket_layout([], 16) == []


def test_bucket_layout_group_break():
    # unequal group ids force a bucket boundary (the faithful path's
    # per-dtype stacks)
    assert bucket_layout([4, 4, 4], 100, ["f32", "f32", "bf16"]) \
        == [[0, 1], [2]]


def test_bucket_layout_rejects_nonpositive_cap():
    with pytest.raises(ValueError, match="bucket_elems"):
        bucket_layout([4], 0)


def test_bucket_plan_key_is_hashable_and_layout_sensitive():
    t = {"a": np.zeros(30, np.float32), "b": np.zeros(30, np.float32)}
    p1 = BucketPlan.for_tree(t, 30)
    p2 = BucketPlan.for_tree(t, 60)
    assert hash(p1.key()) != hash(p2.key()) or p1.key() != p2.key()
    assert p1.n_buckets == 2 and p2.n_buckets == 1
    assert p1.starts == (0, 30)


# ------------------------------------------------ sum_gradients-level parity

def _run_overlapped(mesh, tree, *, mode, bucket_elems, key=None,
                    use_kahan=False, use_aps=False, exp=5, man=2,
                    verify=False, stats=False, block_scale=False,
                    block_size=128):
    """Reduce `tree`'s per-rank rows through the overlap taps: params of
    ones, loss = sum(p * data), so each rank's cotangent IS its data
    row — the reduced grads equal sum_gradients(data rows)."""
    plan = BucketPlan.for_tree({k: v[0] for k, v in tree.items()},
                               bucket_elems=bucket_elems)
    n_out = 2 if (verify or stats) else 1

    def body(st):
        params = jax.tree.map(lambda g: jnp.ones_like(g[0]), st)
        data = jax.tree.map(lambda g: g[0], st)

        def loss_fn(p):
            loss = sum((p[k] * data[k]).sum() for k in p)
            return loss, loss

        (loss, _), grads, rep = overlapped_grads(
            loss_fn, params, axis_name="dp", plan=plan,
            reduce_kw=dict(use_aps=use_aps, grad_exp=exp, grad_man=man,
                           use_kahan=use_kahan, mode=mode,
                           rounding=("stochastic" if key is not None
                                     else "nearest"),
                           bucket_elems=bucket_elems,
                           block_scale=block_scale,
                           block_size=block_size),
            key=key, verify=verify, stats=stats)
        if rep is not None:
            return grads, dict(rep)
        return grads

    fn = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("dp"),),
        out_specs=((P(),) * n_out if n_out > 1 else P()),
        check_vma=False))
    return fn(_shard(mesh, tree))


def _reference(mesh, tree, **kw):
    from cpd_tpu.parallel import make_sum_gradients_fn
    fn = make_sum_gradients_fn(mesh, axis_name="dp", **kw)
    return fn(_shard(mesh, tree))


@pytest.mark.parametrize("world", [2, 4, 8])
@pytest.mark.parametrize("exp,man", [(5, 2), (4, 3)])
@pytest.mark.parametrize("variant", ["nearest", "stochastic", "kahan"])
def test_overlap_bitwise_invariance_faithful(world, exp, man, variant):
    """Per-leaf == bucketed == overlapped for the faithful path, across
    formats x world sizes x rounding — the elementwise ordered scan plus
    global-offset SR bits make the result layout-independent."""
    mesh = make_mesh(dp=world, devices=jax.devices()[:world])
    tree = _tree(world, seed=world + exp)
    kahan = variant == "kahan"
    key = _KEY if variant == "stochastic" else None
    kw = dict(grad_exp=exp, grad_man=man, use_kahan=kahan)
    if key is not None:
        kw.update(rounding="stochastic", key=key)
    per_leaf = _reference(mesh, tree, bucket=False, **kw)
    bucketed = _reference(mesh, tree, bucket_elems=40, **kw)
    overlapped = _run_overlapped(mesh, tree, mode="faithful",
                                 bucket_elems=40, key=key, exp=exp,
                                 man=man, use_kahan=kahan)
    for name in tree:
        _bitwise(per_leaf[name], bucketed[name], f"bucketed {name}")
        _bitwise(per_leaf[name], overlapped[name], f"overlapped {name}")


@pytest.mark.parametrize("world,bucket_elems", [(1, 40), (1, None),
                                                (4, None)])
def test_overlap_taps_follow_the_faithful_layouts(world, bucket_elems):
    """The taps call `sum_gradients` per bucket, so they take the
    faithful path's layouts with it: over one rank no codec and no
    gather, with no cap one bucket of the whole tree; APS on, so the
    wire would carry packed bytes.  Bitwise the per-leaf monolith."""
    mesh = make_mesh(dp=world, devices=jax.devices()[:world])
    tree = _tree(world, seed=11 + world)
    per_leaf = _reference(mesh, tree, bucket=False, use_aps=True,
                          grad_exp=5, grad_man=2)
    overlapped = _run_overlapped(mesh, tree, mode="faithful",
                                 bucket_elems=bucket_elems, use_aps=True)
    for name in tree:
        _bitwise(per_leaf[name], overlapped[name], f"overlapped {name}")


@pytest.mark.parametrize("variant", ["nearest", "stochastic", "kahan"])
def test_overlap_bitwise_invariance_ring(variant):
    """Ring overlap on/off at a FIXED bucket layout is bitwise equal
    (the layout, not the schedule, defines the accumulation order)."""
    mesh = data_parallel_mesh()
    tree = _tree(W, seed=3)
    kahan = variant == "kahan"
    key = _KEY if variant == "stochastic" else None
    kw = dict(grad_exp=5, grad_man=2, use_kahan=kahan, mode="ring",
              bucket_elems=40)
    if key is not None:
        kw.update(rounding="stochastic", key=key)
    post = _reference(mesh, tree, **kw)
    overlapped = _run_overlapped(mesh, tree, mode="ring",
                                 bucket_elems=40, key=key,
                                 use_kahan=kahan)
    for name in tree:
        _bitwise(post[name], overlapped[name], name)


@pytest.mark.parametrize("variant", ["nearest", "stochastic", "kahan"])
def test_overlap_bitwise_invariance_ring_block_scaled(variant):
    """ISSUE 9 acceptance: overlap on/off stays bitwise identical with
    block scaling enabled — blocks are chunk-local, so the per-bucket
    taps reproduce the monolith's block boundaries exactly."""
    mesh = data_parallel_mesh()
    tree = _tree(W, seed=5)
    kahan = variant == "kahan"
    key = _KEY if variant == "stochastic" else None
    kw = dict(grad_exp=4, grad_man=3, use_kahan=kahan, mode="ring",
              bucket_elems=40, block_scale=True, block_size=16)
    if key is not None:
        kw.update(rounding="stochastic", key=key)
    post = _reference(mesh, tree, **kw)
    overlapped = _run_overlapped(mesh, tree, mode="ring",
                                 bucket_elems=40, key=key, exp=4, man=3,
                                 use_kahan=kahan, block_scale=True,
                                 block_size=16)
    for name in tree:
        _bitwise(post[name], overlapped[name], name)


def test_train_step_block_scale_bitwise_and_validated():
    """make_train_step(block_scale=True): overlap on/off bitwise at the
    step level, and the builder rejects non-ring / reduce_in_update."""
    from cpd_tpu.train import make_train_step
    mesh, model, tx, state0, xs, ys = _tiny_setup()
    kw = dict(use_aps=True, grad_exp=4, grad_man=3, mode="ring",
              bucket_elems=100, block_scale=True, block_size=32,
              donate=False)
    mono = make_train_step(model, tx, mesh, **kw)
    over = make_train_step(model, tx, mesh, overlap_reduce=True, **kw)
    sa, _ = mono(state0, xs, ys)
    sb, _ = over(state0, xs, ys)
    for pa, pb in zip(jax.tree.leaves(sa.params),
                      jax.tree.leaves(sb.params)):
        _bitwise(pa, pb, "block-scaled overlap step != monolith")
    with pytest.raises(ValueError, match="mode='ring'"):
        make_train_step(model, tx, mesh, mode="faithful",
                        block_scale=True)


def test_overlap_report_parity_with_monolith():
    """The verify/stats counters decoded from the tap-cotangent channel
    equal the monolith's report values (per-bucket sums/ANDs of the same
    psum-agreed counts)."""
    from cpd_tpu.parallel.dist import sum_gradients
    mesh = data_parallel_mesh()
    tree = _tree(W, seed=4)

    def mono_body(st):
        local = jax.tree.map(lambda g: g[0], st)
        red, rep = sum_gradients(local, "dp", use_aps=True, grad_exp=5,
                                 grad_man=2, mode="ring", verify=True,
                                 stats=True, bucket_elems=40)
        return dict(rep)

    mono = jax.jit(shard_map(mono_body, mesh=mesh, in_specs=(P("dp"),),
                             out_specs=P(), check_vma=False))(
        _shard(mesh, tree))
    _, orep = _run_overlapped(mesh, tree, mode="ring", bucket_elems=40,
                              use_aps=True, verify=True, stats=True)
    for f in ("hop_bad", "gather_bad", "agree", "wire_sat",
              "wire_underflow", "wire_nan", "wire_total", "aps_bad"):
        assert float(orep[f]) == float(mono[f]), (f, orep, mono)
    assert set(REPORT_FIELDS) <= set(orep)


def test_overlap_unused_param_bucket_reports_clean():
    """A bucket whose parameters the loss never touches has its tap
    DCE'd by autodiff: its gradients are zeros (bitwise what reducing
    zeros yields), and the 'ran' sentinel keeps its empty report row
    from reading as a cross-replica disagreement — the verify verdict
    must stay ok=1 on a clean wire (the review-confirmed false-positive
    that would livelock the transport ladder)."""
    mesh = data_parallel_mesh()
    tree = _tree(W, seed=8)
    plan = BucketPlan.for_tree({k: v[0] for k, v in tree.items()},
                               bucket_elems=40)
    assert plan.n_buckets == 3

    def body(st):
        params = jax.tree.map(lambda g: jnp.ones_like(g[0]), st)
        data = jax.tree.map(lambda g: g[0], st)

        def loss_fn(p):
            # leaf "b" (its own bucket) is UNUSED by the loss
            loss = (p["a"] * data["a"]).sum() + (p["c"] * data["c"]).sum()
            return loss, loss

        (_, _), grads, rep = overlapped_grads(
            loss_fn, params, axis_name="dp", plan=plan,
            reduce_kw=dict(use_aps=False, grad_exp=5, grad_man=2,
                           use_kahan=False, mode="ring",
                           rounding="nearest", bucket_elems=40),
            verify=True)
        return grads, dict(rep)

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                           out_specs=(P(), P()), check_vma=False))
    grads, rep = fn(_shard(mesh, tree))
    assert int(rep["ok"]) == 1 and int(rep["agree"]) == 1, \
        jax.tree.map(int, rep)
    # the unused leaf's "reduced" gradient is exactly zeros — bitwise
    # what the monolith's reduce of zero cotangents produces
    _bitwise(grads["b"], np.zeros((53,), np.float32))


def test_overlap_default_bucket_cap_matches_monolith(monkeypatch):
    """bucket_elems=None must mean the SAME layout on both schedules
    (the review-confirmed contract break: taps defaulted to 4M-bucket
    rings while the monolith rang the whole tree).  Shrinking the shared
    default so this small tree spans several buckets, overlap(None) must
    still equal monolith(None) bitwise."""
    import cpd_tpu.parallel.dist as dist_mod
    import cpd_tpu.parallel.overlap as overlap_mod
    monkeypatch.setattr(overlap_mod, "DEFAULT_BUCKET_ELEMS", 40)
    monkeypatch.setattr(dist_mod, "_BUCKET_ELEMS", 40)
    mesh = data_parallel_mesh()
    tree = _tree(W, seed=11)
    post = _reference(mesh, tree, grad_exp=5, grad_man=2, mode="ring")
    overlapped = _run_overlapped(mesh, tree, mode="ring",
                                 bucket_elems=None)
    for name in tree:
        _bitwise(post[name], overlapped[name], name)
    # and the shrunken default really did split the transport: a run at
    # an explicit whole-tree cap accumulates in a different order
    whole = _reference(mesh, tree, grad_exp=5, grad_man=2, mode="ring",
                       bucket_elems=10 ** 9)
    assert any((np.asarray(whole[n]).view(np.uint32)
                != np.asarray(post[n]).view(np.uint32)).any()
               for n in tree)


def test_overlap_unused_bucket_stats_denominator_matches_monolith():
    """quant_stats under overlap must report the monolith's wire_total
    even when a bucket's tap was DCE'd (its zero grads are still probed
    and counted by the monolith) — the precision supervisor's
    saturation-rate denominator cannot depend on the schedule."""
    from cpd_tpu.parallel.dist import sum_gradients
    mesh = data_parallel_mesh()
    tree = _tree(W, seed=12)
    plan = BucketPlan.for_tree({k: v[0] for k, v in tree.items()},
                               bucket_elems=40)

    def body(st):
        params = jax.tree.map(lambda g: jnp.ones_like(g[0]), st)
        data = jax.tree.map(lambda g: g[0], st)

        def loss_fn(p):
            loss = (p["a"] * data["a"]).sum() + (p["c"] * data["c"]).sum()
            return loss, loss

        (_, _), _, rep = overlapped_grads(
            loss_fn, params, axis_name="dp", plan=plan,
            reduce_kw=dict(use_aps=False, grad_exp=5, grad_man=2,
                           use_kahan=False, mode="ring",
                           rounding="nearest", bucket_elems=40),
            stats=True)
        # the monolith probes the WHOLE gradient tree, leaf "b"'s zero
        # cotangents included
        grads = {"a": data["a"], "b": jnp.zeros_like(data["b"]),
                 "c": data["c"]}
        _, mrep = sum_gradients(grads, "dp", grad_exp=5, grad_man=2,
                                mode="ring", stats=True, bucket_elems=40)
        return dict(rep), dict(mrep)

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                           out_specs=(P(), P()), check_vma=False))
    orep, mrep = fn(_shard(mesh, tree))
    for f in ("wire_total", "wire_sat", "wire_underflow", "wire_nan"):
        assert float(orep[f]) == float(mrep[f]), (f, orep, mrep)
    assert float(orep["wire_total"]) == (37 + 53 + 11) * W


def test_bucket_plan_rejects_nonpositive_cap():
    with pytest.raises(ValueError, match="bucket_elems"):
        BucketPlan.for_tree({"a": np.zeros(4, np.float32)}, 0)


def test_overlapped_grads_rejects_mismatched_plan():
    plan = BucketPlan.for_tree({"a": np.zeros(4, np.float32)})
    with pytest.raises(ValueError, match="leaves"):
        overlapped_grads(lambda p: (p["a"].sum(), None),
                         {"a": jnp.zeros(4), "b": jnp.zeros(4)},
                         axis_name="dp", plan=plan, reduce_kw={})


# ------------------------------------------------ train-step parity

def _tiny_setup():
    from cpd_tpu.models.tiny import tiny_cnn
    from cpd_tpu.parallel.dist import replicate
    from cpd_tpu.train import (create_train_state, make_optimizer,
                               warmup_step_decay)
    mesh = data_parallel_mesh()
    model = tiny_cnn(num_classes=4, width=4)
    tx = make_optimizer("sgd", warmup_step_decay(0.1, 10, [100]),
                        momentum=0.9)
    state = replicate(create_train_state(
        model, tx, jnp.zeros((2, 8, 8, 3)), jax.random.PRNGKey(0)), mesh)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 8, 8, 3), jnp.float32)
    y = jnp.asarray(np.arange(16) % 4, jnp.int32)
    return mesh, model, tx, state, x, y


def _overlap_twins():
    """The whole jitted step, monolith and overlapped, at ring + SR (the
    maximal pipeline)."""
    from cpd_tpu.train import make_train_step
    mesh, model, tx, state, x, y = _tiny_setup()
    kw = dict(use_aps=True, grad_exp=5, grad_man=2, mode="ring",
              grad_rounding="stochastic", grad_seed=5, bucket_elems=100,
              donate=False)
    mono = make_train_step(model, tx, mesh, **kw)
    over = make_train_step(model, tx, mesh, overlap_reduce=True, **kw)
    return mono, over, state, x, y


def test_train_step_overlap_bitwise():
    """Overlapped params == monolith params bitwise, metrics equal."""
    mono, over, state, x, y = _overlap_twins()
    sa, ma = mono(state, x, y)
    sb, mb = over(state, x, y)
    for pa, pb in zip(jax.tree.leaves(sa.params),
                      jax.tree.leaves(sb.params)):
        _bitwise(pa, pb)
    assert float(ma["loss"]) == float(mb["loss"])


def test_train_step_overlap_interleaved():
    """The overlap structurally happened — transport collectives
    interleave with backward compute in the tapped program only."""
    mono, over, state, x, y = _overlap_twins()
    ev_o = overlap_evidence(over, state, x, y)
    ev_m = overlap_evidence(mono, state, x, y)
    assert ev_o["interleaved"] and ev_o[
        "compute_after_first_collective"] > 0, ev_o
    assert not ev_m["interleaved"], ev_m


def test_train_step_overlap_sat_pressure_still_fires():
    """The FaultPlan sat_pressure attack rides the tap aux input: the
    pressured overlapped step equals the pressured monolith bitwise (the
    2^k scale lands on every cotangent BEFORE its bucket's reduce)."""
    from cpd_tpu.resilience import FaultPlan
    from cpd_tpu.train import make_train_step
    mesh, model, tx, state, x, y = _tiny_setup()
    # default exponent (2^24), APS off: the probe cast of the W-scaled
    # pressured grads saturates e5m2 — APS would rescue the scale and
    # hide the signal
    plan = FaultPlan.parse("sat_pressure@0")
    table = plan.sat_schedule(4)
    kw = dict(grad_exp=5, grad_man=2, mode="faithful",
              bucket_elems=100, donate=False, sat_fault_plan=table,
              quant_stats=True)
    from cpd_tpu.train import make_train_step as mk
    sa, ma = mk(model, tx, mesh, **kw)(state, x, y)
    sb, mb = mk(model, tx, mesh, overlap_reduce=True, **kw)(state, x, y)
    for pa, pb in zip(jax.tree.leaves(sa.params),
                      jax.tree.leaves(sb.params)):
        _bitwise(pa, pb)
    # the pressure drove the probe cast hot in BOTH schedules, equally
    assert float(ma["prec_wire_sat"]) == float(mb["prec_wire_sat"])
    assert float(mb["prec_wire_sat"]) > 0


def test_train_step_overlap_wire_fault_exact_counters():
    """A wire_flip keeps firing under the overlapped bucketed ring —
    injected into bucket 0 only, so the drill counters stay EXACT
    (hop_bad == 1) whatever the bucket count — and report_unfired
    counts the spec as fired on a ring-mode run."""
    from cpd_tpu.resilience import FaultPlan, Injector, report_unfired
    from cpd_tpu.train import make_train_step
    mesh, model, tx, state, x, y = _tiny_setup()
    plan = FaultPlan.parse("wire_flip@0:3")
    wire = plan.wire_schedule(4)
    step = make_train_step(model, tx, mesh, use_aps=True, grad_exp=5,
                           grad_man=2, mode="ring", bucket_elems=100,
                           donate=False, overlap_reduce=True,
                           verify_reduce=True, wire_fault_plan=wire)
    _, m = step(state, x, y)
    assert float(m["reduce_ok"]) == 0.0
    assert float(m["reduce_hop_bad"]) == 1.0, m
    assert float(m["reduce_gather_bad"]) == 1.0, m
    # the wire table is baked into a ring-mode step: the spec FIRED —
    # report_unfired must come back empty (wire_armed=True)
    inj = Injector(plan)
    assert report_unfired(inj, n_steps=4, wire_armed=True) == []
    # ...and a run that never armed the schedule must surface it
    assert report_unfired(Injector(plan), n_steps=4,
                          wire_armed=False) != []


def test_train_step_overlap_rejects_bad_configs():
    from cpd_tpu.train import make_train_step
    mesh, model, tx, state, x, y = _tiny_setup()
    # ISSUE 12 lifted the emulate_node fail-fast: overlap + emulate > 1
    # now BUILDS (the unrolled micro chain feeds the last micro's taps)
    assert callable(make_train_step(model, tx, mesh, overlap_reduce=True,
                                    emulate_node=2, donate=False))
    # ...but reduce_in_update still needs the updater's tap hook
    # (ZeRO-2 wires it via mesh_layout; ZeRO-3 and ad-hoc updaters
    # don't own one)
    with pytest.raises(ValueError, match="tap_reduce"):
        make_train_step(model, tx, mesh, overlap_reduce=True,
                        reduce_in_update=True,
                        update_fn=lambda *a, **k: None)
    # and the hook alone is meaningless without reduce_in_update
    with pytest.raises(ValueError, match="reduce_in_update"):
        make_train_step(model, tx, mesh,
                        tap_reduce=lambda *a, **k: None,
                        update_fn=lambda *a, **k: None)


def test_lm_train_step_overlap_bitwise():
    """LM step on the dp x sp x tp mesh: the sp/tp psums move into the
    taps (leaf_pre) and the result is still bitwise the monolith's."""
    from cpd_tpu.models.transformer import transformer_lm
    from cpd_tpu.train import (create_train_state, make_optimizer,
                               warmup_step_decay)
    from cpd_tpu.train.lm import lm_state_specs, make_lm_train_step
    from jax.sharding import PartitionSpec
    mesh = make_mesh(dp=2, sp=2, tp=2)
    model = transformer_lm(vocab_size=64, d_model=32, n_layers=2,
                           n_heads=4, tp_axis="tp", sp_axis="sp",
                           tp_size=2)
    init_model = transformer_lm(vocab_size=64, d_model=32, n_layers=2,
                                n_heads=4)
    tx = make_optimizer("sgd", warmup_step_decay(0.01, 10, [100]),
                        momentum=0.9)
    state = create_train_state(init_model, tx,
                               jnp.zeros((1, 16), jnp.int32),
                               jax.random.PRNGKey(0))
    state = jax.device_put(state, jax.tree.map(
        lambda s: NamedSharding(mesh, s), lm_state_specs(state),
        is_leaf=lambda s: isinstance(s, PartitionSpec)))
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, 64, (4, 16)), jnp.int32)
    tgts = jnp.asarray(rng.randint(0, 64, (4, 16)), jnp.int32)
    kw = dict(mode="ring", use_aps=True, grad_exp=5, grad_man=2,
              grad_rounding="stochastic", grad_seed=3, donate=False,
              bucket_elems=2000)
    sa, ma = make_lm_train_step(model, tx, mesh, **kw)(state, toks, tgts)
    sb, mb = make_lm_train_step(model, tx, mesh, overlap_reduce=True,
                                **kw)(state, toks, tgts)
    for pa, pb in zip(jax.tree.leaves(sa.params),
                      jax.tree.leaves(sb.params)):
        _bitwise(pa, pb)
    assert float(ma["loss"]) == float(mb["loss"])


def test_lm_train_step_overlap_accepts_emulate_node():
    # ISSUE 12 lifted the LM fail-fast too: overlap + emulate_node > 1
    # builds (the bitwise gate is test_train_step_overlap_emulate_node)
    from cpd_tpu.models.transformer import transformer_lm
    from cpd_tpu.train import make_optimizer, warmup_step_decay
    from cpd_tpu.train.lm import make_lm_train_step
    mesh = data_parallel_mesh()
    model = transformer_lm(vocab_size=8, d_model=8, n_layers=1, n_heads=2)
    tx = make_optimizer("sgd", warmup_step_decay(0.01, 10, [100]))
    assert callable(make_lm_train_step(model, tx, mesh,
                                       overlap_reduce=True,
                                       emulate_node=2, donate=False))


# ------------------------------------------------ ladder-key composition

def test_ladder_step_key_overlap_coordinate():
    """ISSUE 8 satellite: the overlap/bucket coordinate splits the step
    cache; absent (None) keeps the PR 4/5-compatible shapes."""
    from cpd_tpu.resilience import (PrecisionSupervisor, StepTable,
                                    TransportSupervisor, ladder_step_key)
    from cpd_tpu.resilience.precision import resolve_ladder_key
    t = TransportSupervisor(start="ring")
    p = PrecisionSupervisor("e5m2,e5m7")
    base = ladder_step_key(t, p, overlap=None, block=None)
    assert base == ("ring", (5, 2))          # PR 5 shape preserved
    k1 = ladder_step_key(t, p, overlap=(True, 65536), block=None)
    k2 = ladder_step_key(t, p, overlap=(False, None), block=None)
    assert k1 != k2 != base and k1 != base
    assert k1 == (("ring", (5, 2)), ("overlap", True, 65536))
    # resolve strips the coordinate and recovers (level, fmt)
    assert resolve_ladder_key(
        k1, transport_on=True, precision_on=True, level="ring",
        fmt=(5, 2), overlap_on=True) == ("ring", (5, 2))
    assert resolve_ladder_key(
        ladder_step_key(t, None, overlap=(True, None), block=None),
        transport_on=True, precision_on=False, level="ring", fmt=(5, 2),
        overlap_on=True) == ("ring", (5, 2))
    # distinct keys -> distinct StepTable entries (no stale-step serve)
    built = []
    table = StepTable(lambda key: built.append(key) or (lambda *a: key))
    assert table[k1] is not table[k2]
    assert built == [k1, k2]


def test_ladder_step_key_block_coordinate():
    """ISSUE 9 satellite: the block-scaled wire is its own accumulation
    numerics, so the (block_scale, block_size) coordinate must split
    the step cache the same way the overlap coordinate does — and
    compose with it (block appended outermost)."""
    from cpd_tpu.resilience import (PrecisionSupervisor, StepTable,
                                    TransportSupervisor, ladder_step_key)
    from cpd_tpu.resilience.precision import resolve_ladder_key
    t = TransportSupervisor(start="ring")
    p = PrecisionSupervisor("e5m2,e5m7")
    base = ladder_step_key(t, p, overlap=None, block=None)
    assert base == ("ring", (5, 2))          # PR 8 shape preserved
    kb = ladder_step_key(t, p, overlap=None, block=(True, 128))
    assert kb == (("ring", (5, 2)), ("block", True, 128))
    assert kb != ladder_step_key(t, p, overlap=None,
                                 block=(True, 32)) != base
    both = ladder_step_key(t, p, overlap=(True, 65536),
                           block=(True, 128))
    assert both == ((("ring", (5, 2)), ("overlap", True, 65536)),
                    ("block", True, 128))
    # resolve strips block (then overlap) and recovers (level, fmt)
    assert resolve_ladder_key(
        kb, transport_on=True, precision_on=True, level="ring",
        fmt=(5, 2), block_on=True) == ("ring", (5, 2))
    assert resolve_ladder_key(
        both, transport_on=True, precision_on=True, level="ring",
        fmt=(5, 2), overlap_on=True, block_on=True) == ("ring", (5, 2))
    # distinct keys -> distinct StepTable entries
    built = []
    table = StepTable(lambda key: built.append(key) or (lambda *a: key))
    assert table[kb] is not table[both]
    assert built == [kb, both]


def test_make_sum_gradients_fn_cache_keyed_by_block_coordinate():
    """The standalone reducer's jit cache key carries the block
    coordinates — a callable traced for the blocked wire must never
    serve the per-tensor config (the PR 5 half-keyed-table bug class,
    extended to the block coordinate)."""
    from cpd_tpu.parallel import make_sum_gradients_fn
    mesh = data_parallel_mesh()
    tree = _tree(W, seed=11)
    f1 = make_sum_gradients_fn(mesh, axis_name="dp", grad_exp=4,
                               grad_man=3, mode="ring", block_scale=True,
                               block_size=32)
    f2 = make_sum_gradients_fn(mesh, axis_name="dp", grad_exp=4,
                               grad_man=3, mode="ring")
    sharded = _shard(mesh, tree)
    f1(sharded)
    f2(sharded)
    (k1,) = list(f1._cache._d)
    (k2,) = list(f2._cache._d)
    assert k1 != k2
    assert k1[3] is True and k1[4] == 32     # the block coordinates
    assert k2[3] is False


def test_make_sum_gradients_fn_cache_keyed_by_bucket_layout():
    """The standalone reducer's jit cache must not serve a callable
    traced for one bucket layout to another (same treedef!)."""
    from cpd_tpu.parallel import make_sum_gradients_fn
    mesh = data_parallel_mesh()
    tree = _tree(W, seed=9)
    f1 = make_sum_gradients_fn(mesh, axis_name="dp", grad_exp=5,
                               grad_man=2, mode="ring", bucket_elems=40)
    f2 = make_sum_gradients_fn(mesh, axis_name="dp", grad_exp=5,
                               grad_man=2, mode="ring")
    sharded = _shard(mesh, tree)
    f1(sharded)
    f2(sharded)
    (k1,) = list(f1._cache._d)
    (k2,) = list(f2._cache._d)
    assert k1 != k2
    assert k1[2] == 40 and k2[2] is None   # the bucket coordinate


# ------------------------------------------------ emulate-node overlap
# (ISSUE 12 leg 3: the micro-batch scan's barrier is gone — the first
# N-1 micros run unrolled and feed the LAST micro's taps as extras)

@pytest.mark.slow
def test_train_step_overlap_emulate_node_bitwise():
    """overlap on/off at emulate_node=2 with the full pipeline on (APS +
    SR + ring): PARAMS bitwise identical to the scan + post-backward
    monolith (the transport claim — every gradient bit, emulate reduce
    included, matches), metrics equal.  BN running stats are pinned at
    ulp tolerance instead: XLA compiles the monolith's scanned forward
    and the overlap path's unrolled micro chain with different fusions,
    and a batch-mean reduction can differ in the last ulp — forward
    compilation noise, orthogonal to the reduction semantics under
    test (the params being bitwise proves the GRADS were)."""
    from cpd_tpu.train import make_train_step
    mesh, model, tx, state, x, y = _tiny_setup()
    x2 = jnp.concatenate([x, x[::-1]])   # 32 = 16 * emulate_node
    y2 = jnp.concatenate([y, y[::-1]])
    kw = dict(use_aps=True, grad_exp=5, grad_man=2, mode="ring",
              grad_rounding="stochastic", grad_seed=5, bucket_elems=100,
              emulate_node=2, donate=False)
    mono = make_train_step(model, tx, mesh, **kw)
    over = make_train_step(model, tx, mesh, overlap_reduce=True, **kw)
    sa, ma = mono(state, x2, y2)
    sb, mb = over(state, x2, y2)
    for pa, pb in zip(jax.tree.leaves(sa.params),
                      jax.tree.leaves(sb.params)):
        _bitwise(pa, pb, "emulate-node overlap step != monolith")
    for pa, pb in zip(jax.tree.leaves(sa.batch_stats),
                      jax.tree.leaves(sb.batch_stats)):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                   rtol=1e-6, atol=1e-8)
    assert float(ma["loss"]) == float(mb["loss"])
    assert float(ma["accuracy"]) == float(mb["accuracy"])


@pytest.mark.slow
def test_train_step_overlap_emulate_node_interleaved():
    """overlap_evidence on the emulate>1 tapped step: the dp transport
    collectives interleave with the LAST micro-batch's backward compute
    (the monolith's scan postdates every collective)."""
    from cpd_tpu.train import make_train_step
    mesh, model, tx, state, x, y = _tiny_setup()
    x2 = jnp.concatenate([x, x[::-1]])
    y2 = jnp.concatenate([y, y[::-1]])
    kw = dict(use_aps=True, grad_exp=5, grad_man=2, mode="ring",
              bucket_elems=100, emulate_node=2, donate=False)
    mono = make_train_step(model, tx, mesh, **kw)
    over = make_train_step(model, tx, mesh, overlap_reduce=True, **kw)
    ev_mono = overlap_evidence(mono, state, x2, y2)
    ev_over = overlap_evidence(over, state, x2, y2)
    assert not ev_mono["interleaved"]
    assert ev_over["interleaved"], ev_over


@pytest.mark.slow
def test_lm_train_step_overlap_emulate_node_bitwise():
    """LM step on the dp x sp x tp mesh at emulate_node=2: the unrolled
    micro chain + tap-side emulate reduce reproduce the scanned
    monolith bit for bit (sp/tp psums, sat-free path, SR)."""
    from cpd_tpu.models.transformer import transformer_lm
    from cpd_tpu.train import (create_train_state, make_optimizer,
                               warmup_step_decay)
    from cpd_tpu.train.lm import lm_state_specs, make_lm_train_step
    from jax.sharding import PartitionSpec
    mesh = make_mesh(dp=2, sp=2, tp=2)
    model = transformer_lm(vocab_size=64, d_model=32, n_layers=2,
                           n_heads=4, tp_axis="tp", sp_axis="sp",
                           tp_size=2)
    init_model = transformer_lm(vocab_size=64, d_model=32, n_layers=2,
                                n_heads=4)
    tx = make_optimizer("sgd", warmup_step_decay(0.01, 10, [100]),
                        momentum=0.9)
    state = create_train_state(init_model, tx,
                               jnp.zeros((1, 16), jnp.int32),
                               jax.random.PRNGKey(0))
    state = jax.device_put(state, jax.tree.map(
        lambda s: NamedSharding(mesh, s), lm_state_specs(state),
        is_leaf=lambda s: isinstance(s, PartitionSpec)))
    rng = np.random.RandomState(2)
    toks = jnp.asarray(rng.randint(0, 64, (8, 16)), jnp.int32)
    tgts = jnp.asarray(rng.randint(0, 64, (8, 16)), jnp.int32)
    kw = dict(use_aps=True, grad_exp=5, grad_man=2,
              grad_rounding="stochastic", grad_seed=3, donate=False,
              bucket_elems=2000, emulate_node=2)
    sa, ma = make_lm_train_step(model, tx, mesh, **kw)(state, toks, tgts)
    sb, mb = make_lm_train_step(model, tx, mesh, overlap_reduce=True,
                                **kw)(state, toks, tgts)
    for pa, pb in zip(jax.tree.leaves(sa.params),
                      jax.tree.leaves(sb.params)):
        _bitwise(pa, pb, "LM emulate-node overlap != monolith")
    assert float(ma["loss"]) == float(mb["loss"])
