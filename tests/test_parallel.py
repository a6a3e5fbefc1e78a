"""Tests for the distributed layer (cpd_tpu.parallel).

Oracle strategy (SURVEY.md §4): NumPy transliterations of the reference's
Python loops (dist_util.py:54-89, mix.py:251-282) checked bit-for-bit against
the JAX implementations, on an 8-device virtual CPU platform (conftest.py) —
the JAX analog of the reference's `--emulate_node` testing trick.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from cpd_tpu.compat import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from cpd_tpu.parallel import (aps_max_exponents, aps_shift_factors,
                              data_parallel_mesh, emulate_node_reduce,
                              kahan_quantized_sum, make_mesh,
                              make_sum_gradients_fn, ordered_quantized_sum,
                              replicate, sum_gradients)
from cpd_tpu.quant import float_quantize

W = 8  # conftest forces 8 virtual devices


def np_quant(x, exp, man):
    """Host-side quantize via the JAX cast (itself oracle-tested in
    test_numerics.py against the CUDA transliteration)."""
    return np.asarray(float_quantize(jnp.asarray(x, jnp.float32), exp, man))


def oracle_normal_sum(grads, exp, man):
    # dist_util.py:60-69
    res = np.zeros_like(grads[0])
    for g in grads:
        res = np_quant(res + g, exp, man)
    return res


def oracle_kahan_sum(grads, exp, man):
    # dist_util.py:72-89
    res = np.zeros_like(grads[0])
    c = np.zeros_like(grads[0])
    for g in grads:
        y = np_quant(g - c, exp, man)
        t = np_quant(res + y, exp, man)
        c = np_quant(np_quant(t - res, exp, man) - y, exp, man)
        res = t
    return res


def rand_stack(shape, seed=0, scale=1.0):
    rng = np.random.RandomState(seed)
    return (rng.randn(W, *shape) * scale).astype(np.float32)


@pytest.mark.parametrize("exp,man", [(5, 2), (4, 3), (5, 10), (8, 23)])
def test_ordered_sum_matches_oracle(exp, man):
    stacked = rand_stack((17, 5), seed=1)
    got = np.asarray(ordered_quantized_sum(jnp.asarray(stacked), exp, man))
    want = oracle_normal_sum(list(stacked), exp, man)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("exp,man", [(5, 2), (4, 3), (8, 23)])
def test_kahan_sum_matches_oracle(exp, man):
    stacked = rand_stack((33,), seed=2)
    got = np.asarray(kahan_quantized_sum(jnp.asarray(stacked), exp, man))
    want = oracle_kahan_sum(list(stacked), exp, man)
    np.testing.assert_array_equal(got, want)


def test_kahan_beats_plain_at_low_precision():
    # The reason Kahan exists (README.md:10-11): compensated accumulation
    # tracks the true sum better at e5m2.
    stacked = rand_stack((1000,), seed=3, scale=0.1)
    true = stacked.astype(np.float64).sum(0)
    plain = np.asarray(ordered_quantized_sum(jnp.asarray(stacked), 5, 2))
    kahan = np.asarray(kahan_quantized_sum(jnp.asarray(stacked), 5, 2))
    assert (np.abs(kahan - true).mean() <= np.abs(plain - true).mean())


def _shard_stacked(mesh, stacked_tree):
    """Place leaves (W, ...) with leading axis on the dp mesh axis."""
    return jax.tree.map(
        lambda g: jax.device_put(
            jnp.asarray(g), NamedSharding(mesh, P("dp"))), stacked_tree)


@pytest.mark.parametrize("use_kahan", [False, True])
@pytest.mark.parametrize("use_aps", [False, True])
def test_sum_gradients_collective_matches_oracle(use_aps, use_kahan):
    exp, man = 5, 2
    mesh = data_parallel_mesh()
    tree = {"w": rand_stack((9, 4), seed=4), "b": rand_stack((7,), seed=5)}

    reduce_fn = make_sum_gradients_fn(mesh, axis_name="dp", use_aps=use_aps,
                                      grad_exp=exp, grad_man=man,
                                      use_kahan=use_kahan)
    got = jax.tree.map(np.asarray, reduce_fn(_shard_stacked(mesh, tree)))

    # Oracle: dist_util.py:22-51 literally.
    def oracle(stacked):
        grads = {k: list(v) for k, v in stacked.items()}
        shifts = {}
        if use_aps:
            for k, gs in grads.items():
                max_exp = max(
                    np.ceil(np.log2(np.abs(g * np.float32(W)).max()))
                    for g in gs)
                shifts[k] = (2 ** (exp - 1) - 1) - max_exp
                grads[k] = [np_quant(g * 2.0 ** shifts[k], exp, man)
                            for g in gs]
        fn = oracle_kahan_sum if use_kahan else oracle_normal_sum
        out = {k: fn(gs, exp, man) for k, gs in grads.items()}
        if use_aps:
            out = {k: (v / np.float32(2.0 ** shifts[k])).astype(np.float32)
                   for k, v in out.items()}
        return out

    want = oracle(tree)
    for k in tree:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


@pytest.mark.parametrize("use_kahan", [False, True])
def test_bucketed_faithful_reduce_bit_identical(use_kahan):
    """Fusing leaves into buckets (one gather + one ordered scan per bucket,
    SURVEY.md §7 hard-part 4) must not change a single bit vs the per-leaf
    path — the quantized accumulation is elementwise.  A tiny bucket cap
    forces multiple buckets, including a leaf larger than the cap."""
    from cpd_tpu.parallel.dist import _faithful_quantized_sum

    mesh = data_parallel_mesh()
    exp, man = 4, 3
    tree = {"a": rand_stack((37,), seed=10), "b": rand_stack((100,), seed=11),
            "c": rand_stack((5, 9), seed=12), "d": rand_stack((3,), seed=13)}

    def body(stacked, bucketed):
        local = jax.tree.map(lambda g: g[0], stacked)
        if bucketed:
            return _faithful_quantized_sum(local, "dp", exp, man, use_kahan,
                                           bucket_elems=64)
        return sum_gradients(local, "dp", grad_exp=exp, grad_man=man,
                             use_kahan=use_kahan, bucket=False)

    in_spec = jax.tree.map(lambda _: P("dp"), tree)
    out_spec = jax.tree.map(lambda _: P(), tree)
    sharded = {k: jax.device_put(v, NamedSharding(mesh, P("dp")))
               for k, v in tree.items()}
    got = {}
    for bucketed in (False, True):
        fn = jax.jit(shard_map(
            functools.partial(body, bucketed=bucketed), mesh=mesh,
            in_specs=(in_spec,), out_specs=out_spec, check_vma=False))
        got[bucketed] = jax.tree.map(np.asarray, fn(sharded))
    for k in tree:
        np.testing.assert_array_equal(got[True][k], got[False][k],
                                      err_msg=k)


@pytest.mark.parametrize("exp,man", [(5, 2), (4, 3), (8, 7), (5, 10)])
def test_wire_compressed_gather_bit_identical(exp, man):
    """With APS the gathered values live in the (exp, man) value set, so
    shipping them as bit-packed eXmY code words (pack_exmy) on the wire
    must not change a single bit of the reduction result.  (4,3) — which
    the old hardware-dtype table could not map, e4m3fn having no inf —
    now compresses too."""
    from cpd_tpu.parallel.dist import _wire_format

    from cpd_tpu.parallel.dist import _gather_leaf
    from cpd_tpu.parallel.reduction import quantized_sum
    from cpd_tpu.quant.numerics import cast_to_format

    wire = _wire_format(exp, man)
    assert wire == (exp, man)
    assert _wire_format(8, 23) is None       # 4-byte words: nothing to gain
    mesh = data_parallel_mesh()
    # mixed magnitudes incl. values that quantize to subnormals and (via
    # a huge outlier) to inf in the target format
    g = rand_stack((257,), seed=20, scale=1e-3)
    g[0, 0] = 1e30
    g[1, 1] = -1e30

    def body(stacked, use_wire):
        local = cast_to_format(stacked[0], exp, man)   # pre-quantized
        gathered = _gather_leaf(local, "dp", wire=wire if use_wire else None)
        return quantized_sum(gathered, exp, man)

    sharded = jax.device_put(jnp.asarray(g), NamedSharding(mesh, P("dp")))
    got = {}
    for use_wire in (False, True):
        fn = jax.jit(shard_map(
            functools.partial(body, use_wire=use_wire), mesh=mesh,
            in_specs=(P("dp"),), out_specs=P(), check_vma=False))
        got[use_wire] = np.asarray(fn(sharded))
    np.testing.assert_array_equal(got[True], got[False])


def test_sum_gradients_fp32_is_plain_sum():
    mesh = data_parallel_mesh()
    tree = {"w": rand_stack((6, 3), seed=6)}
    reduce_fn = make_sum_gradients_fn(mesh, axis_name="dp",
                                      grad_exp=8, grad_man=23)
    got = np.asarray(reduce_fn(_shard_stacked(mesh, tree))["w"])
    np.testing.assert_allclose(got, tree["w"].sum(0), rtol=1e-6)


def test_sum_gradients_fast_mode_precision():
    # fast mode: quantize -> psum -> quantize.  Oracle: quantize each rank's
    # grad, fp32 sum (psum's order variation is sub-ulp here), final cast.
    mesh = data_parallel_mesh()
    tree = {"w": rand_stack((32,), seed=7)}
    fast = make_sum_gradients_fn(mesh, axis_name="dp", grad_exp=5, grad_man=2,
                                 mode="fast")
    a = np.asarray(fast(_shard_stacked(mesh, tree))["w"])
    q_each = np.stack([np_quant(g, 5, 2) for g in tree["w"]])
    want = np_quant(q_each.sum(0), 5, 2)
    assert np.isfinite(a).all()
    np.testing.assert_allclose(a, want, rtol=0.3, atol=1e-6)


def test_aps_zero_grad_guard():
    # All-zero leaf: reference sum_gradients would NaN (log2(0) = -inf,
    # dist_util.py:27); we guard (shift=0) like the emulate path
    # (mix.py:267-268).  Result must be zeros, not NaN.
    mesh = data_parallel_mesh()
    tree = {"z": np.zeros((W, 5), np.float32)}
    reduce_fn = make_sum_gradients_fn(mesh, axis_name="dp", use_aps=True,
                                      grad_exp=5, grad_man=2)
    got = np.asarray(reduce_fn(_shard_stacked(mesh, tree))["z"])
    np.testing.assert_array_equal(got, np.zeros(5, np.float32))


def test_aps_improves_low_precision_sum():
    # The paper's point: APS rescues *dynamic range*.  Gradients below
    # e5m2's subnormal floor (2^-16) vanish in an unshifted quantized sum;
    # the exponent shift moves them to the top of the representable range.
    stacked = rand_stack((256,), seed=8, scale=1e-6)
    true = stacked.astype(np.float64).sum(0)

    plain = np.asarray(ordered_quantized_sum(jnp.asarray(stacked), 5, 2))

    mesh = data_parallel_mesh()
    aps = make_sum_gradients_fn(mesh, axis_name="dp", use_aps=True,
                                grad_exp=5, grad_man=2)
    got = np.asarray(aps(_shard_stacked(mesh, {"g": stacked}))["g"])
    assert np.abs(got - true).mean() < np.abs(plain - true).mean()


@pytest.mark.parametrize("use_aps", [False, True])
def test_emulate_node_matches_oracle(use_aps):
    # mix.py:251-282 literally.
    exp, man, n = 5, 2, 4
    rng = np.random.RandomState(9)
    stacked = (rng.randn(n, 13) * 0.01).astype(np.float32)

    got = np.asarray(emulate_node_reduce(
        {"g": jnp.asarray(stacked)}, n, use_aps=use_aps,
        grad_exp=exp, grad_man=man)["g"])

    max_exp = np.ceil(np.log2(np.abs(stacked * np.float32(n)).max()))
    shift = (2 ** (exp - 1) - 1) - max_exp if use_aps else 0.0
    q = [np_quant(g * 2.0 ** shift, exp, man) for g in stacked]
    res = np.zeros_like(q[0])
    for g in q:
        res = np_quant(res + g, exp, man)
    want = (res / np.float32(2.0 ** shift)).astype(np.float32)
    np.testing.assert_array_equal(got, want)


def test_emulate_node_one_is_identity():
    g = rand_stack((5,), seed=10)[:1]
    got = np.asarray(emulate_node_reduce({"g": jnp.asarray(g)}, 1,
                                         use_aps=True, grad_exp=5,
                                         grad_man=2)["g"])
    np.testing.assert_array_equal(got, g[0])  # mix.py:254-256: no quantize


def test_replicate_and_mesh_axes():
    mesh = make_mesh(dp=2, tp=2, sp=2)
    assert mesh.shape == {"dp": 2, "pp": 1, "sp": 2, "ep": 1, "tp": 2}
    tree = {"w": np.ones((4, 4), np.float32)}
    rep = replicate(tree, mesh)
    assert rep["w"].sharding.is_fully_replicated

    mesh0 = make_mesh(dp=0, tp=4)
    assert mesh0.shape["dp"] == 2 and mesh0.shape["tp"] == 4


def test_collective_matches_emulation_bit_exact():
    # The design invariant: real collectives and emulate-node use the same
    # ordered primitive, so an 8-rank collective reduction == an
    # emulate_node=8 local reduction (sans APS-shift differences when both
    # disabled).
    exp, man = 4, 3
    stacked = rand_stack((21,), seed=11)
    mesh = data_parallel_mesh()
    coll = make_sum_gradients_fn(mesh, axis_name="dp", grad_exp=exp,
                                 grad_man=man)
    a = np.asarray(coll(_shard_stacked(mesh, {"g": stacked}))["g"])
    b = np.asarray(ordered_quantized_sum(jnp.asarray(stacked), exp, man))
    np.testing.assert_array_equal(a, b)


def test_group_split_subcommunicators():
    """group_split == reference simple_group_split (train_util.py:11-18):
    consecutive-rank groups, usable as axis_index_groups in collectives."""
    from cpd_tpu.parallel import group_split

    groups = group_split(8, 2)
    assert groups == [[0, 1, 2, 3], [4, 5, 6, 7]]
    with pytest.raises(ValueError):
        group_split(8, 3)

    mesh = data_parallel_mesh()

    def body(x):
        return jax.lax.psum(x, "dp", axis_index_groups=groups)

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                           out_specs=P("dp"), check_vma=False))
    x = jnp.arange(8.0)
    out = np.asarray(fn(x))
    # group sums: 0+1+2+3=6 for ranks 0-3, 4+5+6+7=22 for ranks 4-7
    np.testing.assert_array_equal(out, [6, 6, 6, 6, 22, 22, 22, 22])


# --------------------------------------------------------------------------
# The faithful path gives a leaf the wire's layout only where there is a
# wire (dist.faithful_plan): one rank -> no codec, no gather, no flattening;
# several ranks -> a leaf alone in its bucket crosses the wire in its own
# shape.  Each rule is held bit for bit (uint32 patterns) to the composition
# it replaces, and the ordered sum to a `lax.scan` written out here.
# --------------------------------------------------------------------------

FORMATS = [(5, 2), (4, 3), (5, 10), (8, 7)]
ROUNDINGS = ["nearest", "stochastic"]


def bits(tree):
    return jax.tree.map(lambda a: np.asarray(a).view(np.uint32), tree)


def assert_same_bits(got, want):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(bits(got)),
                            jax.tree.leaves(bits(want))):
        np.testing.assert_array_equal(g, w, err_msg=str(path))


def _eqns(jaxpr):
    """Every equation, sub-programs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def edge_values(exp, man):
    """-0.0, the format's subnormals, its largest normal, a value that
    rounds up past it (the carry value 2^(emax+1)), +-Inf and NaN."""
    emax = 2 ** (exp - 1) - 1
    min_normal = 2.0 ** (1 - emax)
    top = 2.0 ** emax
    return np.asarray(
        [-0.0, 0.0, min_normal, -min_normal, min_normal * 2.0 ** -man,
         -min_normal * 2.0 ** -man, min_normal * 2.0 ** -(man + 1),
         min_normal * 0.75, top * (2 - 2.0 ** -man),
         top * (2 - 2.0 ** -(man + 1)), -top * (2 - 2.0 ** -(man + 1)),
         np.inf, -np.inf, np.nan, 1.0, -3.0], np.float32)


def edge_tree(world, exp, man, seed):
    """Stacked (world, ...) leaves: `edge` holds the special values on
    every rank (its APS shift is 0: a leaf with Inf or NaN is not
    scaled); `edge` (64 elements) and `m` (45) are above the 40-element
    cap the tests bucket at, `a` and `b` share a bucket, `z` is left
    alone in one; magnitudes span more than any format's range, so the
    APS shift moves values into subnormals and past the top."""
    rng = np.random.RandomState(seed)
    edge = np.tile(edge_values(exp, man), (world, 4)).reshape(world, 4, 16)
    with np.errstate(over="ignore"):
        edge[:, 1:] *= rng.choice([0.5, 1.0, 2.0], size=(world, 3, 16))

    def rand(*shape):
        return (rng.randn(world, *shape)
                * 10.0 ** rng.uniform(-8, 2, size=(world,) + shape)
                ).astype(np.float32)

    return {"a": rand(7), "b": rand(3, 4), "edge": edge.astype(np.float32),
            "m": rand(9, 5), "z": rand(11)}


def run_reduce(mesh, tree, body):
    """jit(shard_map(body)) over `mesh`'s dp axis on stacked leaves."""
    spec = jax.tree.map(lambda _: P("dp"), tree)
    fn = jax.jit(shard_map(
        lambda st: body(jax.tree.map(lambda g: g[0], st)), mesh=mesh,
        in_specs=(spec,), out_specs=jax.tree.map(lambda _: P(), tree),
        check_vma=False))
    return fn(_shard_stacked(mesh, tree))


def sr_key(rounding):
    return jax.random.PRNGKey(7) if rounding == "stochastic" else None


def _one_rank_mesh():
    return make_mesh(dp=1, devices=jax.devices()[:1])


def _one_rank_tree(exp, man):
    tree = edge_tree(1, exp, man, seed=exp * 31 + man)
    return {k: tree[k] for k in ("a", "edge", "m")}


@functools.lru_cache(maxsize=None)
def _one_rank_spelled_out(exp, man, use_aps, use_kahan, rounding):
    """The path the one-rank rule replaces, spelled out: scale, cast,
    flatten, pack, gather over the one rank, unpack, the ordered sum with
    global SR offsets, reshape back, unscale."""
    from cpd_tpu.parallel.aps import (aps_scale, aps_shift_factors_checked,
                                      aps_unscale, pmax_scalar_vector)
    from cpd_tpu.parallel.dist import (_flat_axis_index, _leaf_offsets,
                                       _leaf_starts, _wire_format,
                                       quantize_tree_sr)
    from cpd_tpu.parallel.reduction import quantized_sum
    from cpd_tpu.quant.numerics import pack_exmy, unpack_exmy

    key = sr_key(rounding)

    def body(grads):
        k_pre = k_sum = None
        if key is not None:
            k_pre, k_sum, _ = jax.random.split(key, 3)
            k_pre = jax.random.fold_in(k_pre, _flat_axis_index("dp"))
        if use_aps:
            world = jax.lax.psum(jnp.float32(1.0), "dp")
            shifts, _ = aps_shift_factors_checked(pmax_scalar_vector(
                aps_max_exponents(grads, world), "dp"), exp)
            grads = quantize_tree_sr(aps_scale(grads, shifts), exp, man,
                                     k_pre)
        starts = _leaf_starts(grads)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        out = []
        for st, g in zip(starts, leaves):
            flat = g.reshape(-1)
            if use_aps and _wire_format(exp, man):
                gathered = unpack_exmy(jax.lax.all_gather(
                    pack_exmy(flat, exp, man), "dp"), exp, man)
            else:
                gathered = jax.lax.all_gather(flat, "dp")
            red = quantized_sum(
                gathered, exp, man, use_kahan, key=k_sum,
                offsets=(None if k_sum is None
                         else _leaf_offsets(st, g).ravel()))
            out.append(red.reshape(g.shape))
        reduced = jax.tree_util.tree_unflatten(treedef, out)
        return aps_unscale(reduced, shifts) if use_aps else reduced

    return bits(run_reduce(_one_rank_mesh(), _one_rank_tree(exp, man), body))


@pytest.mark.parametrize("bucket", [None, True, 40],
                         ids=["auto", "bucket", "cap40"])
@pytest.mark.parametrize("rounding", ROUNDINGS)
@pytest.mark.parametrize("use_kahan", [False, True], ids=["plain", "kahan"])
@pytest.mark.parametrize("use_aps", [False, True], ids=["noaps", "aps"])
@pytest.mark.parametrize("exp,man", FORMATS)
def test_one_rank_reduction_is_the_codec_composition(exp, man, use_aps,
                                                     use_kahan, rounding,
                                                     bucket):
    """Over an axis of one rank `sum_gradients` runs no codec, no gather
    and no flattening, and gives the bits of the path it replaces
    (`_one_rank_spelled_out`); an explicit `bucket=True` or
    `bucket_elems=` changes nothing there: the same program, equation
    for equation, so the same bits."""
    tree = _one_rank_tree(exp, man)

    def reduce(kw):
        return lambda g: sum_gradients(
            g, "dp", use_aps=use_aps, grad_exp=exp, grad_man=man,
            use_kahan=use_kahan, rounding=rounding, key=sr_key(rounding),
            **kw)

    if bucket is not None:
        kw = dict(bucket_elems=40) if bucket == 40 else dict(bucket=True)
        local = jax.tree.map(lambda g: g[0], tree)
        programs = [str(jax.make_jaxpr(shard_map(
            reduce(k), mesh=_one_rank_mesh(), in_specs=P(), out_specs=P(),
            check_vma=False))(local)) for k in (kw, {})]
        assert programs[0] == programs[1]
        return
    got = run_reduce(_one_rank_mesh(), tree, reduce({}))
    assert_same_bits(got, _one_rank_spelled_out(exp, man, use_aps,
                                                use_kahan, rounding))
    # the special values took part, and survived as what they are
    edge = np.asarray(got["edge"])
    assert np.isnan(edge[0, 13]) and np.isinf(edge[0, 11])


@pytest.mark.parametrize("variant", ["rtne", "kahan", "sr", "noaps"])
@pytest.mark.parametrize("exp,man", FORMATS)
@pytest.mark.parametrize("world", [4, 8])
def test_own_shape_leaf_across_ranks_matches_per_leaf(world, exp, man,
                                                      variant):
    """Several ranks, one leaf above the cap among small ones: the large
    leaf is packed, gathered and unpacked in its own shape, the small
    ones concatenated; every bit equals the per-leaf path's
    (`bucket=False`), SR bits by global offset included.  `noaps`
    gathers raw fp32 (no codec), as the reference does without APS."""
    mesh = make_mesh(dp=world, devices=jax.devices()[:world])
    tree = edge_tree(world, exp, man, seed=world * 100 + exp * 31 + man)
    tree = {k: tree[k] for k in ("a", "b", "edge")}    # 7, 12, 64 elements
    rounding = "stochastic" if variant == "sr" else "nearest"
    got = {}
    for name, kw in (("per_leaf", dict(bucket=False)),
                     ("planned", dict(bucket_elems=40))):
        got[name] = run_reduce(mesh, tree, lambda g, kw=kw: sum_gradients(
            g, "dp", use_aps=variant != "noaps", grad_exp=exp, grad_man=man,
            use_kahan=variant == "kahan", rounding=rounding,
            key=sr_key(rounding), **kw))
    assert_same_bits(got["planned"], got["per_leaf"])


def _scan_ordered(stacked, exp, man, key=None, offsets=None,
                  block_size=None):
    """`ordered_quantized_sum` as a `lax.scan`, whatever the length."""
    from cpd_tpu.parallel.reduction import _make_q
    q = _make_q(exp, man, key, offsets, block=block_size)

    def step(carry, g):
        res, i = carry
        return (q(res + g, i, 0), i + 1), None

    return jax.lax.scan(step, (jnp.zeros_like(stacked[0]),
                               jnp.zeros([], jnp.int32)), stacked)[0][0]


def _scan_kahan(stacked, exp, man, key=None, offsets=None, block_size=None):
    """`kahan_quantized_sum` as a `lax.scan`, whatever the length."""
    from cpd_tpu.parallel.reduction import _make_q
    q = _make_q(exp, man, key, offsets, block=block_size)

    def step(carry, g):
        res, c, i = carry
        y = q(g - c, i, 0)
        t = q(res + y, i, 1)
        return (t, q(q(t - res, i, 2) - y, i, 3), i + 1), None

    zero = jnp.zeros_like(stacked[0])
    return jax.lax.scan(step, (zero, zero, jnp.zeros([], jnp.int32)),
                        stacked)[0][0]


@pytest.mark.parametrize("variant", ["rtne", "sr", "block32"])
@pytest.mark.parametrize("rows", [1, 2, 4, 8, 9])
@pytest.mark.parametrize("fn,scan", [
    (ordered_quantized_sum, _scan_ordered),
    (kahan_quantized_sum, _scan_kahan)], ids=["ordered", "kahan"])
def test_ordered_sum_matches_the_written_out_scan(fn, scan, rows, variant):
    """The ordered sums are one `lax.scan` over the leading axis whatever
    its length, with the bits of the chain written out here: the faithful
    path's layouts change what reaches the sum, never the sum."""
    exp, man = (4, 3) if variant == "block32" else (5, 2)
    kw = {"sr": dict(key=jax.random.PRNGKey(3)),
          "block32": dict(block_size=32)}.get(variant, {})
    rng = np.random.RandomState(rows)
    stacked = np.tile(edge_values(exp, man), (rows, 3, 4))
    stacked = jnp.asarray(stacked * rng.choice(
        [0.25, 1.0, 3.0], size=stacked.shape).astype(np.float32))

    def summed(s):
        return fn(s, exp, man, **kw)

    assert_same_bits(jax.jit(summed)(stacked),
                     jax.jit(lambda s: scan(s, exp, man, **kw))(stacked))
    loops = [e for e in _eqns(jax.make_jaxpr(summed)(stacked).jaxpr)
             if e.primitive.name in ("scan", "while")]
    assert [e.params["length"] for e in loops] == [rows]


def _lm_leaf_sizes():
    """Leaf sizes of the benchmark's StarCoder2-3B cut (published widths,
    4 layers): shapes only, nothing is initialised."""
    from cpd_tpu.models import transformer_lm
    model = transformer_lm(vocab_size=49152, d_model=3072, n_layers=4,
                           n_heads=24, n_kv_heads=2, d_ff=12288)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    return [l.size for l in jax.tree.leaves(params)]


def _resnet50_leaf_sizes():
    from cpd_tpu.models import resnet50
    variables = jax.eval_shape(lambda: resnet50(num_classes=1000).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)), train=False))
    return [l.size for l in jax.tree.leaves(variables["params"])]


@pytest.mark.parametrize("world", [1, 4, 32])
@pytest.mark.parametrize("sizes_of", [_lm_leaf_sizes, _resnet50_leaf_sizes],
                         ids=["starcoder2_3b_d4", "resnet50"])
def test_faithful_plan_on_the_benchmarks_leaf_sizes(sizes_of, world):
    from cpd_tpu.parallel.dist import _BUCKET_ELEMS, faithful_plan

    sizes = sizes_of()
    plan = faithful_plan(sizes, world, _BUCKET_ELEMS)
    large = {i for i, n in enumerate(sizes) if n >= _BUCKET_ELEMS}
    covered = sorted(plan["own_shape_leaves"]
                     + tuple(i for b in plan["buckets"] for i in b))
    assert covered == list(range(len(sizes)))    # each leaf exactly once
    own_elems = sum(sizes[i] for i in plan["own_shape_leaves"])
    if world == 1:
        # no wire: nothing is packed, gathered or bucketed
        assert not plan["codec"] and not plan["buckets"]
        assert own_elems == sum(sizes)
        assert plan == faithful_plan(sizes, 1, None)
        return
    assert plan["codec"] and large <= set(plan["own_shape_leaves"])
    assert all(len(b) > 1 and sum(sizes[i] for i in b) <= _BUCKET_ELEMS
               for b in plan["buckets"])
    if sizes_of is _lm_leaf_sizes:
        assert (len(sizes), sum(sizes), len(large)) == (39, 534829056, 17)
        assert own_elems >= 0.98 * sum(sizes)
    else:
        # every ResNet-50 leaf is under the cap: all stay bucketed, which
        # is what their launch count wants
        assert len(sizes) == 161 and not large
        assert not plan["own_shape_leaves"] and len(plan["buckets"]) == 8


def test_faithful_plan_groups_and_per_leaf():
    from cpd_tpu.parallel.dist import faithful_plan

    sizes = [10, 10, 50, 10, 10]
    # no cap (bucket=False): every leaf gathered alone, in its own shape
    assert faithful_plan(sizes, 4, None)["own_shape_leaves"] == (0, 1, 2, 3, 4)
    plan = faithful_plan(sizes, 4, 40)
    assert plan["own_shape_leaves"] == (2,)
    assert plan["buckets"] == ((0, 1), (3, 4))
    # leaves of different groups never share a bucket, and are grouped
    # across the tree, not broken at every change
    plan = faithful_plan(sizes, 4, 40, groups=["f", "h", "f", "h", "f"])
    assert plan["buckets"] == ((1, 3),)
    assert plan["own_shape_leaves"] == (0, 2, 4)


def _reduce_jaxpr(world, tree, **kw):
    mesh = make_mesh(dp=world, devices=jax.devices()[:world])
    spec = jax.tree.map(lambda _: P("dp"), tree)
    fn = shard_map(
        lambda st: sum_gradients(jax.tree.map(lambda g: g[0], st), "dp",
                                 mode="faithful", grad_exp=5, grad_man=2,
                                 **kw),
        mesh=mesh, in_specs=(spec,),
        out_specs=jax.tree.map(lambda _: P(), tree), check_vma=False)
    return jax.make_jaxpr(fn)(jax.tree.map(jnp.asarray, tree))


@pytest.mark.parametrize("kw", [
    dict(), dict(bucket=True), dict(bucket_elems=40),
    dict(use_kahan=True),
    dict(rounding="stochastic", key=jax.random.PRNGKey(1), bucket_elems=40)],
    ids=["auto", "bucket", "cap40", "kahan", "sr"])
def test_one_rank_program_has_no_wire(kw):
    """At axis size 1 the traced program of the faithful APS reduction
    holds no collective of the gradient, no byte, no concatenate of
    leaves and no dynamic_slice, and its ordered sums are scans of one
    trip (which XLA inlines): only the `pmax` of the APS exponents (over
    one rank, which XLA removes) names the axis."""
    from cpd_tpu.obs import scopes

    tree = edge_tree(1, 5, 2, seed=0)
    jaxpr = _reduce_jaxpr(1, tree, use_aps=True, **kw)
    eqns = list(_eqns(jaxpr.jaxpr))
    prims = {e.primitive.name for e in eqns}
    assert not prims & {"all_gather", "dynamic_slice", "while",
                        "all_to_all", "ppermute"}, prims
    assert {e.params["length"] for e in eqns
            if e.primitive.name == "scan"} == {1}
    assert not any(v.aval.dtype == jnp.uint8
                   for e in eqns for v in e.outvars)
    # the one concatenate stacks the leaves' APS exponents
    assert all(e.outvars[0].aval.shape == (len(tree),) for e in eqns
               if e.primitive.name == "concatenate")
    assert any(scopes.REDUCE_LOCAL in str(e.source_info.name_stack)
               for e in eqns)
    assert not any(s in str(e.source_info.name_stack) for e in eqns
                   for s in (scopes.WIRE_PACK, scopes.WIRE_UNPACK,
                             scopes.WIRE_COLLECTIVE))


@pytest.mark.parametrize("use_kahan", [False, True], ids=["plain", "kahan"])
def test_four_rank_program_keeps_a_large_leaf_in_its_shape(use_kahan):
    """At four ranks with a cap of 40 the 45-element leaf `m` (9, 5) is
    never flattened: no value of 45 elements a rank is one-dimensional
    and one `uint8` gather carries it in its own shape."""
    tree = edge_tree(4, 5, 2, seed=0)
    jaxpr = _reduce_jaxpr(4, tree, use_aps=True, use_kahan=use_kahan,
                          bucket_elems=40)
    eqns = list(_eqns(jaxpr.jaxpr))
    shapes = {tuple(v.aval.shape) for e in eqns for v in e.outvars}
    assert (45,) not in shapes and (4, 45) not in shapes
    gathers = [e for e in eqns if e.primitive.name == "all_gather"]
    assert all(e.outvars[0].aval.dtype == jnp.uint8 for e in gathers)
    # `edge`, `m` and `z` cross alone, `a` and `b` share one flat bucket
    assert sorted(tuple(e.outvars[0].aval.shape) for e in gathers) == [
        (4, 4, 16, 1), (4, 9, 5, 1), (4, 11, 1), (4, 19, 1)]
