"""Distributed layer (L2): low-precision gradient all-reduce over a mesh axis.

TPU-native re-implementation of reference CPDtorch/utils/dist_util.py on top
of XLA collectives.  The reference runs one NCCL op per parameter from a
Python loop; here everything is traced once under `shard_map`/`pjit` so XLA
schedules the collectives on ICI back-to-back (and can overlap them).  On
TPU the faithful gathers of small leaves are fused into few large per-dtype
buckets, and when APS has pre-quantized the values the wire carries the
bit-packed eXmY code words (`quant.numerics.pack_exmy`, 1-3 bytes per
element for any sub-fp32 format) — both bit-identical to the per-leaf
fp32 path.  A leaf is given the wire's layout (flat, concatenated, packed)
only where a wire needs it (`faithful_plan`): over an axis of ONE rank
there is no codec, no gather and no flattening at all, so the ordered sum
is a few elementwise operations in the leaf's own shape that XLA fuses
into the optimizer's pass; over several ranks a leaf that fills a bucket
alone is packed, gathered and unpacked in its own shape.

Semantics map (reference → here):

    dist_init()                 → `dist_init()` (jax.distributed/env-driven;
                                  no SLURM hostname surgery — the TPU runtime
                                  provides coordination)         dist_util.py:96-131
    DistModule/broadcast_params → `replicate(tree, mesh)` (replicated
                                  sharding *is* the broadcast) + in-graph
                                  `broadcast_from(x, axis_name, src)`
                                                                 dist_util.py:8-19,92-94
    sum_gradients(...)          → `sum_gradients(grads, axis_name=...)`
                                  (pytree-in/pytree-out, pure)   dist_util.py:22-51
    normal/kahan_sum_gradients  → all_gather + ordered scan (reduction.py)
                                                                 dist_util.py:54-89

Reduction modes:

* ``faithful`` (default): bit-faithful emulation — `all_gather` the fp32
  gradients, then rank-ordered requantized accumulation.  Costs W× bandwidth
  exactly like the reference's all_gather (dist_util.py:62-64); order *is*
  the semantics.
* ``fast``: quantize → `psum` → no dequantize-step emulation.  The
  deployment path (EQuARX-style): same precision at the wire, but XLA's
  reduction tree order, so not bit-identical to the reference.  New
  capability beyond the reference.
* ``ring``: chunked ppermute reduce-scatter + all-gather moving bit-packed
  eXmY partials (parallel/ring.py) — the ordered requantized reduction at
  ~2/W of the gather path's wire elements and O(n/W) peak transient
  memory, in the documented per-chunk rank-rotation order (bitwise-gated
  by `ring.ring_oracle_sum`).  New capability beyond the reference.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import scopes
from ..quant.numerics import (cast_to_format, cast_to_format_sr_at,
                              pack_exmy, unpack_exmy, wire_bytes)
from ..quant.quant_function import tree_quant_health
from .aps import (aps_max_exponents, aps_scale, aps_shift_factors_checked,
                  aps_unscale, pmax_scalar_vector)
from .overlap import DEFAULT_BUCKET_ELEMS, bucket_layout
from .reduction import quantized_sum
from .ring import hierarchical_ring_sum

__all__ = [
    "dist_init", "sum_gradients", "broadcast_from", "replicate",
    "all_reduce_mean", "host_batch_to_global", "quantize_tree_sr",
    "grad_sr_key", "faithful_plan",
]


def dist_init(coordinator_address: Optional[str] = None,
              num_processes: Optional[int] = None,
              process_id: Optional[int] = None) -> tuple[int, int]:
    """Initialize multi-host JAX and return (rank, world_size).

    Replaces reference `dist_init` (dist_util.py:96-131).  The reference
    hand-parses SLURM_NODELIST to find a TCP master and hardcodes port 12345;
    `jax.distributed.initialize` auto-detects SLURM / OpenMPI / TPU-pod
    environments, so the hostname surgery disappears.  Single-process runs
    (no cluster env) are a no-op returning (0, 1) — unlike the reference,
    which raises outside SLURM (dist_util.py:97-98)."""
    import os
    explicit = coordinator_address is not None
    in_cluster = any(v in os.environ for v in
                     ("SLURM_PROCID", "OMPI_COMM_WORLD_RANK",
                      "COORDINATOR_ADDRESS", "TPU_WORKER_ID"))
    if explicit or in_cluster:
        if not jax.distributed.is_initialized():
            # No blanket except here: a coordinator failure must surface,
            # not silently degrade an N-host job to N independent trainings.
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id)
    return jax.process_index(), jax.process_count()


def replicate(tree: Any, mesh: Mesh) -> Any:
    """Place a pytree fully-replicated on every device of `mesh`.

    The functional equivalent of reference `broadcast_params`
    (dist_util.py:92-94) + `DistModule.__init__` (dist_util.py:8-12): with a
    replicated NamedSharding, every device holds rank-0's bytes — the
    broadcast happens in the transfer."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)


def broadcast_from(x: jnp.ndarray, axis_name: str, src: int = 0) -> jnp.ndarray:
    """In-graph broadcast of `src`'s shard to all ranks along `axis_name`.

    For use inside shard_map when parity with an explicit
    `dist.broadcast(p, 0)` (dist_util.py:94) is wanted mid-computation."""
    return lax.all_gather(x, axis_name, axis=0, tiled=False)[src]


def host_batch_to_global(x, mesh: Mesh, axis_name: str = "dp"):
    """Assemble each host's local batch slice into one global jax.Array
    sharded over `axis_name`.

    Multi-controller JAX feeds data per process (the analog of the
    reference's per-rank DataLoader, main.py:111-120): each host loads
    global_batch / process_count consecutive samples and this stitches them
    into the global batch.  Single-process: a plain device_put.  The
    host-order convention matches the contiguous per-rank blocks of
    DistributedGivenIterationSampler (train_util.py:212-215)."""
    x = np.asarray(x)
    sharding = NamedSharding(mesh, P(axis_name))
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    return jax.make_array_from_process_local_data(sharding, x)


def all_reduce_mean(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Mean across an axis — the loss/metric averaging the examples do with
    all_reduce + divide (mix.py:240-242, main.py:167-169)."""
    return lax.pmean(x, axis_name)


def _flat_axis_index(axis_name) -> jnp.ndarray:
    """This rank's flat index along one axis name or a sequence of them
    (row-major over the sequence), for per-rank SR key decorrelation."""
    if isinstance(axis_name, str):
        return lax.axis_index(axis_name)
    idx = jnp.zeros([], jnp.int32)
    for a in axis_name:
        idx = idx * lax.psum(jnp.int32(1), a) + lax.axis_index(a)
    return idx


def _leaf_starts(tree) -> list[int]:
    """Static global flat offset of each leaf (tree_flatten order) — the
    index space the SR bitstream is defined on.  parallel/zero.py flattens
    the same tree in the same order, so its shard offsets index the same
    space and reproduce the same bits."""
    sizes = [l.size for l in jax.tree_util.tree_leaves(tree)]
    return [0] + list(np.cumsum(sizes[:-1]).astype(np.int64)) if sizes else []


def _leaf_offsets(start: int, leaf) -> jnp.ndarray:
    """Global flat offsets for one leaf, shaped like the leaf."""
    return (jnp.uint32(start)
            + jnp.arange(leaf.size, dtype=jnp.uint32)).reshape(leaf.shape)


def quantize_tree_sr(tree, grad_exp: int, grad_man: int, key,
                     starts: Optional[Sequence[int]] = None) -> Any:
    """Per-leaf eXmY cast of a pytree: RTNE when `key` is None, otherwise
    stochastic rounding with GLOBAL-offset-indexed bits (one bitstream over
    the concatenated flat layout, so the draw is identical however the
    tree is later flattened, bucketed, or sharded).  ``starts`` overrides
    each leaf's global flat offset — for callers whose ``tree`` is a
    SLICE of a larger layout (the overlap taps reduce one bucket at a
    time, parallel/overlap.py) and must draw that layout's bits."""
    if key is None:
        return jax.tree.map(
            lambda g: cast_to_format(g, grad_exp, grad_man), tree)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    starts = _leaf_starts(tree) if starts is None else list(starts)
    out = [cast_to_format_sr_at(g, grad_exp, grad_man, key,
                                _leaf_offsets(st, g))
           for st, g in zip(starts, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def grad_sr_key(grad_seed: int, step, site: int) -> jax.Array:
    """The ONE derivation of gradient-pipeline SR keys; the gradient
    stage (train/grads.py) calls it for every train-step builder.

    Depends only on (grad_seed, step, site) — NEVER a rank index: the
    same key must reach every sp/tp/pp/ep copy so replicated leaves
    round identically (desynchronized bits would silently diverge
    optimizer state across copies).  `sum_gradients` itself folds the
    dp rank into its pre-quantize subkey where decorrelation is wanted.
    Site convention: 0 = the rank-local pre-reduce cast (emulate-node;
    callers fold their dp rank in AFTER this), 1 = the cross-device
    `sum_gradients` reduction."""
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(grad_seed), step), site)


def _wire_format(grad_exp: int, grad_man: int):
    """(exp, man) when shipping the bit-packed eXmY code words pays, else
    None.

    When the gathered values are ALREADY quantized to the format (the APS
    path quantizes before the reduction, dist_util.py:35-37),
    `pack_exmy`'s re-encoding is lossless and the wire carries
    ``wire_bytes(exp, man)`` (1-3) bytes/element instead of 4.  This
    replaces the old 3-entry hardware-dtype table: ANY sub-fp32 format
    with man >= 2 compresses now — including (4,3), which float8_e4m3fn
    (finite-only) could never carry because the reference cast saturates
    to ±inf."""
    if grad_man >= 2 and wire_bytes(grad_exp, grad_man) < 4:
        return (grad_exp, grad_man)
    return None


def _gather_leaf(g: jnp.ndarray, axis_name, wire=None) -> jnp.ndarray:
    """all_gather one leaf; `wire` is an (exp, man) tuple to bit-pack the
    payload (values must already be in that format's value set)."""
    if wire is not None:
        packed = pack_exmy(g, *wire)
        with jax.named_scope(scopes.WIRE_COLLECTIVE):
            out = lax.all_gather(packed, axis_name, axis=0, tiled=False)
        return unpack_exmy(out, *wire)
    with jax.named_scope(scopes.WIRE_COLLECTIVE):
        return lax.all_gather(g, axis_name, axis=0, tiled=False)


# Per-bucket element cap for the faithful path (one home for the number:
# parallel/overlap.py, which the overlapped transport shares the layout
# with).  W x 4M x 4B = 128 MiB of gathered fp32 at W=8 — large enough to
# amortize collective launch overhead, small enough that the gathered
# stack never rivals model memory.
_BUCKET_ELEMS = DEFAULT_BUCKET_ELEMS


def _axis_size(axis_name) -> int:
    """Static size of one mesh axis, or the product over a sequence of
    them: `lax.psum` of a Python constant is evaluated at trace time."""
    return lax.psum(1, axis_name)


def faithful_plan(sizes: Sequence[int], world: int,
                  bucket_elems: Optional[int] = None,
                  groups: Optional[Sequence] = None) -> dict:
    """Which layout each leaf of a faithful reduction gets — a pure
    function of what the trace-time code can see (leaf sizes, axis size,
    bucket cap), which `_faithful_quantized_sum` itself calls to decide,
    so a count made from it cannot drift from the path.

    * ``world == 1``: no wire.  Every leaf is reduced in its own shape
      with no codec and no collective, whatever ``bucket_elems`` says
      (buckets exist to save collective launches).
    * ``world > 1``, ``bucket_elems=None``: the per-leaf path — every
      leaf gathered in its own shape.
    * ``world > 1`` with a cap: `overlap.bucket_layout` per group
      (``groups``: one hashable per leaf, e.g. its dtype; leaves of
      different groups never share a bucket).  A bucket of ONE leaf —
      every leaf at or above the cap, and a small one the greedy layout
      leaves alone — needs no flattening: it is packed, gathered and
      unpacked in its own shape.  Buckets of several leaves are
      concatenated flat, one gather each.

    Returns what the path consumes: ``codec`` (a wire exists: leaves are
    gathered, bit-packed where the format packs), ``own_shape_leaves``
    (leaf indices) and ``buckets`` (tuples of leaf indices, each of two or
    more)."""
    sizes = [int(n) for n in sizes]
    if world == 1 or bucket_elems is None:
        layout = [[i] for i in range(len(sizes))]
    else:
        # group GLOBALLY (order of first appearance), then cap each group
        # with the shared layout function — an interleaved-dtype tree
        # still packs into few large per-dtype buckets instead of
        # breaking a bucket at every dtype change
        by_group: dict = {}
        for i in range(len(sizes)):
            by_group.setdefault(None if groups is None else groups[i],
                                []).append(i)
        layout = [[idxs[j] for j in local]
                  for idxs in by_group.values()
                  for local in bucket_layout([sizes[i] for i in idxs],
                                             bucket_elems)]
    return {"codec": world > 1,
            "own_shape_leaves": tuple(b[0] for b in layout if len(b) == 1),
            "buckets": tuple(tuple(b) for b in layout if len(b) > 1)}


def _faithful_quantized_sum(grads: Any, axis_name, grad_exp: int,
                            grad_man: int, use_kahan: bool,
                            bucket_elems: Optional[int] = None,
                            wire=None, key=None, starts=None) -> Any:
    """Faithful ordered reduction, each leaf in the layout `faithful_plan`
    gives it: the reference's per-parameter loop (dist_util.py:60-89) with
    its W x leaf_count collective launches collapsed to W x bucket_count
    (SURVEY.md §7 hard-part 4), and no collective at all over one rank.

    The quantized accumulation is elementwise, so neither concatenation
    nor a leaf's shape changes any element's value, and the codec is the
    identity on values the pre-quantize produced (numerics "Losslessness
    contract"): every layout is bit-identical to the per-leaf path, with
    or without the wire.

    With stochastic rounding (`key` given) the per-element bits are indexed
    by GLOBAL flat offset (numerics.sr_bits_at), so every layout draws the
    SAME bits — invariant to bucketing (and to ZeRO sharding,
    parallel/zero.py).  ``starts`` overrides the leaves' global offsets
    (overlap taps reducing a bucket of a larger layout).
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    starts = _leaf_starts(grads) if starts is None else list(starts)
    plan = faithful_plan([l.size for l in leaves], _axis_size(axis_name),
                         bucket_elems,
                         groups=[jnp.dtype(l.dtype) for l in leaves])

    qsum = functools.partial(quantized_sum, exp=grad_exp, man=grad_man,
                             use_kahan=use_kahan, key=key)

    def offsets(i):
        """Leaf i's global SR bit indices, shaped like the leaf."""
        return None if key is None else _leaf_offsets(starts[i], leaves[i])

    out = [None] * len(leaves)
    for i in plan["own_shape_leaves"]:
        if plan["codec"]:
            out[i] = qsum(_gather_leaf(leaves[i], axis_name, wire=wire),
                          offsets=offsets(i))
        else:
            # what a gather over one rank returns, without the gather
            with jax.named_scope(scopes.REDUCE_LOCAL):
                out[i] = qsum(leaves[i][None], offsets=offsets(i))
    for bucket in plan["buckets"]:
        flat = jnp.concatenate([leaves[i].reshape(-1) for i in bucket])
        red = qsum(_gather_leaf(flat, axis_name, wire=wire),
                   offsets=(None if key is None else jnp.concatenate(
                       [offsets(i).ravel() for i in bucket])))
        off = 0
        for i in bucket:
            n = leaves[i].size
            out[i] = lax.dynamic_slice_in_dim(red, off, n).reshape(
                leaves[i].shape)
            off += n
    return jax.tree_util.tree_unflatten(treedef, out)


@jax.named_scope(scopes.REDUCE)
def sum_gradients(grads: Any, axis_name: str | Sequence[str],
                  use_aps: bool = False, grad_exp: int = 5, grad_man: int = 2,
                  use_kahan: bool = False, mode: str = "faithful",
                  bucket: Optional[bool] = None,
                  rounding: str = "nearest", key=None,
                  verify: bool = False,
                  wire_fault: Optional[tuple] = None,
                  stats: bool = False,
                  bucket_elems: Optional[int] = None,
                  offset_starts: Optional[Sequence[int]] = None,
                  block_scale: bool = False,
                  block_size: int = 128) -> Any:
    """Low-precision gradient all-reduce (SUM) over `axis_name`.

    Pure pytree-in/pytree-out version of reference `sum_gradients`
    (dist_util.py:22-51); must be called inside shard_map/pjit with
    `axis_name` bound on the mesh's data axis.  Returns the *sum* (not mean)
    of per-rank gradients, like the reference — trainers pre-divide the loss
    by world_size so the sum is the mean (mix.py:239).

    use_aps     → APS exponent shifting around the reduction (aps.py).
    use_kahan   → Kahan-compensated ordered accumulation (dist_util.py:72-89).
    mode        → "faithful" (gather + ordered sum) | "fast" (quantize+psum)
                  | "ring" (chunked ppermute reduce-scatter + all-gather
                  with bit-packed eXmY partials on the wire — the ordered
                  requantized reduction at ~2/W of the gather wire bytes
                  and O(n/W) peak memory, in parallel/ring.py's documented
                  per-chunk rank-rotation order).  On a MULTI-axis
                  ``axis_name`` the ring composes hierarchically:
                  sequential per-axis rings, innermost (last-named) axis
                  first, bit-gated by `ring.ring_oracle_sum_multi`
                  (parallel/ring.hierarchical_ring_sum) — the old
                  multi-axis fail-fast is gone.
    bucket      → faithful mode only: fuse per-leaf gathers into few large
                  per-dtype buckets (bit-identical).  Default (None) =
                  auto: on for TPU — fewer collective launches riding ICI
                  — off elsewhere (on the CPU mesh the gather is a plain
                  memcpy and the bucket concat/split copies measured ~17%
                  slower on a ResNet-18-sized pytree).  A layout exists
                  for the wire's sake, so it is decided from the axis
                  size and the leaf sizes (`faithful_plan`): over an
                  axis of ONE rank nothing is bucketed, packed or
                  gathered whatever this says (each leaf is summed in
                  its own shape, under the scope `reduce.local`), and
                  over several ranks a leaf left alone in its bucket —
                  every leaf at or above the cap — is packed, gathered
                  and unpacked in its own shape, never flattened.
    bucket_elems→ per-bucket element cap (default `_BUCKET_ELEMS`, 4M).
                  Setting it implies ``bucket=True`` for faithful mode
                  (and, like it, changes nothing over one rank).
                  RING mode is always bucketed at this cap via the same
                  greedy layout the overlapped backward-reduce emits
                  (`overlap.bucket_layout` / `BucketPlan.for_tree`), so
                  overlap on/off is bitwise identical at ANY value
                  including the default — a tree that fits one bucket
                  rings whole, exactly the pre-bucketing transport.
                  NOTE: different ``bucket_elems`` values are DIFFERENT
                  documented accumulation orders (chunk boundaries
                  move), each gated by its own per-bucket oracle.
                  Ignored by "fast" (psum is elementwise; layout-free).
    offset_starts→ per-leaf GLOBAL flat offsets overriding the tree's own
                  `_leaf_starts` — for callers reducing a SLICE of a
                  larger layout (the overlap taps, parallel/overlap.py)
                  whose SR bits must match the whole-layout draw.
    block_scale / block_size → ring mode only: the EQuARX-style
                  block-scaled wire (quant/numerics.py "Block-scaled
                  eXmY codec"): every hop cast shares one power-of-2
                  scale per `block_size` consecutive elements, the
                  1-byte-per-block shift sidecar riding the packed
                  wire.  Different accumulation NUMERICS than the
                  per-tensor cast — gated by its own extended oracle
                  (`ring.ring_oracle_sum(block_scale=True)`), and an
                  e4m3 blocked wire covers dynamic range a per-tensor
                  e5m7 cannot (tools/bench_reduce.py --block-sweep).
                  Needs a packable format (man >= 2, not (8, 23));
                  rejected outside mode="ring" — faithful/fast have no
                  sidecar wire to carry the scales.
    rounding    → "nearest" (reference semantics) | "stochastic": every
                  eXmY cast in the pipeline (the APS/fast pre-quantize,
                  each ordered-accumulation step, the fast post-quantize)
                  uses the unbiased SR cast driven by `key` (required) —
                  sub-ulp/2 gradient mass then survives the reduction in
                  expectation, the unbiased alternative to APS's exponent
                  shifting (beyond-reference; composes with it too).
                  Per-element bits are indexed by (key, scan step, cast
                  site, GLOBAL flat offset) — deterministic given key and
                  invariant to bucketing and to ZeRO reduce-scatter
                  sharding (parallel/zero.py reproduces these exact bits
                  on each shard); every rank derives identical bits, so
                  replicated outputs agree.
    verify      → self-verifying reduction (parallel/integrity.py):
                  returns ``(reduced, report)`` where report holds the
                  replicated int32 scalars {ok, hop_bad, gather_bad,
                  agree}.  Ring mode checks every hop payload and
                  all-gather row against tagged Fletcher checksums AND
                  pmin/pmax-agrees the result digest across replicas;
                  faithful/fast have no checksummable custom wire, so
                  their report is the cross-replica agreement digest
                  alone (hop_bad/gather_bad stay 0).  The clean-path
                  values are bitwise unchanged.
    wire_fault  → ``(code, rank)`` int32 scalars: inject a deterministic
                  wire fault (resilience/inject.WIRE_KINDS) into the
                  ring transport on that rank — ignored outside ring
                  mode, because the wire being attacked IS the ring's
                  (downgrading the transport is how a run escapes a
                  persistently faulty ring wire).
    stats       → numeric-health telemetry of the reduce-wire cast site
                  (quant.numerics.quant_health): returns ``(reduced,
                  report)`` where report gains the psum-agreed
                  float32 scalars {wire_sat, wire_underflow, wire_nan,
                  wire_total} plus ``aps_bad`` (count of leaves whose
                  APS max-exponent was +Inf/NaN — gradients already
                  non-finite BEFORE the wire, satellite of
                  aps_shift_factors_checked; 0 when use_aps is off).
                  With APS the counters observe the pre-reduce quantize
                  that already runs (zero extra casts); without APS the
                  local grads are probe-cast to the wire format once,
                  telemetry-only (RTNE regardless of `rounding` — the
                  probe measures format fit, not round direction; its
                  output is discarded).  The data path is bitwise
                  unchanged either way.  Composes with `verify`: one
                  merged report dict.
    """
    if mode not in ("faithful", "fast", "ring"):
        raise ValueError(f"unknown mode {mode!r}")
    if mode == "ring" and not isinstance(axis_name, str) \
            and not tuple(axis_name):
        raise ValueError("mode='ring' needs at least one mesh axis; got "
                         f"{tuple(axis_name)!r}")
    if rounding not in ("nearest", "stochastic"):
        raise ValueError(f"unknown rounding {rounding!r}")
    if rounding == "stochastic" and key is None:
        raise ValueError("rounding='stochastic' requires a PRNG key "
                         "(fold in the step counter for fresh per-step "
                         "bits)")
    if rounding == "nearest" and key is not None:
        raise ValueError("a PRNG key was passed but rounding='nearest' "
                         "would ignore it; pass rounding='stochastic' "
                         "(matching float_quantize/quant_gemm's contract)")
    if bucket is False and bucket_elems is not None and mode == "faithful":
        raise ValueError("bucket=False contradicts an explicit "
                         "bucket_elems — drop one of them")
    if block_scale and mode != "ring":
        raise ValueError(
            f"block_scale=True needs mode='ring' (got {mode!r}): the "
            f"per-block shift sidecar rides the ring's packed wire — "
            f"faithful's gather and fast's psum have no lane to carry it")
    if bucket is None:
        bucket = (jax.default_backend() == "tpu"
                  or bucket_elems is not None)
    world = lax.psum(jnp.float32(1.0), axis_name)

    # Independent SR bitstreams for the three cast stages.  The pre-
    # quantize acts on each rank's OWN gradients, so its key folds in the
    # rank index — identical bits across ranks would round similar
    # gradients the same way and the summed rounding error would grow
    # coherently (~W*ulp) instead of averaging out (~sqrt(W)*ulp).  The
    # ordered-sum and post-psum casts act on data that is identical on
    # every rank (gathered / reduced), so THEIR keys must stay shared or
    # the replicated outputs would disagree.
    k_pre = k_sum = k_post = None
    if key is not None:
        k_pre, k_sum, k_post = jax.random.split(key, 3)
        k_pre = jax.random.fold_in(k_pre, _flat_axis_index(axis_name))

    def q_tree(t, k):
        with jax.named_scope(scopes.WIRE_CAST):
            return quantize_tree_sr(t, grad_exp, grad_man, k,
                                    starts=offset_starts)

    shifts = None
    prec = None
    aps_bad = jnp.zeros([], jnp.int32)
    if use_aps:
        max_exp = aps_max_exponents(grads, world)
        max_exp = pmax_scalar_vector(max_exp, axis_name)
        # checked variant: a +Inf/NaN max-exponent means the leaf holds
        # non-finite gradients — shift 0 is damage control, the count is
        # the signal (computed on the pmax'd vector, so it is replicated)
        shifts, aps_bad = aps_shift_factors_checked(max_exp, grad_exp)
        scaled = aps_scale(grads, shifts)
        grads = q_tree(scaled, k_pre)
        if stats:
            # the exact values that hit the reduce wire, observed for
            # free: the APS pre-quantize above already ran, telemetry
            # just compares its (input, output) pair
            prec = tree_quant_health(scaled, grads)
    elif stats:
        # no pre-quantize on this path (faithful/ring cast inside the
        # ordered accumulation) — probe: cast the local grads, scaled by
        # the world size, to the wire format once; telemetry-only,
        # result discarded.  The ·W scale is APS's own worst-case bound
        # on the ordered accumulation (max|g·W|, dist_util.py:26-28): a
        # per-rank value can fit the format while the running W-rank sum
        # saturates mid-scan, and the supervisor must see THAT — the
        # failure the reduce actually hits — not just the per-element
        # cast.  This one extra elementwise cast is the measured
        # telemetry overhead of docs/PERF.md.
        scaled = jax.tree.map(lambda g: g.astype(jnp.float32) * world,
                              grads)
        probe = jax.tree.map(
            lambda g: cast_to_format(g, grad_exp, grad_man), scaled)
        prec = tree_quant_health(scaled, probe)

    if mode == "fast":
        if not use_aps and not (grad_exp == 8 and grad_man == 23):
            grads = q_tree(grads, k_pre)
        # fast mode IS the XLA-order psum by definition: same wire
        # precision, no order emulation (module docstring) — the one
        # place the unordered reduction is the documented intent.
        with jax.named_scope(scopes.WIRE_COLLECTIVE):
            reduced = jax.tree.map(  # cpd: disable=kahan-ordering
                lambda g: lax.psum(g, axis_name), grads)
        if not (grad_exp == 8 and grad_man == 23):
            reduced = q_tree(reduced, k_post)
    elif mode == "ring":
        # Per-bucket rings over the flat gradient (ONE whole-tree ring
        # when bucket_elems is None — leaves concatenated in tree_flatten
        # order, SR offsets in the same global space as _leaf_starts).
        # Partial sums are post-quantize — always in the format value set
        # — so the wire is bit-packed whether or not APS pre-quantized
        # the inputs.  Multi-axis axis_name composes hierarchically
        # (ring.hierarchical_ring_sum); an injected wire fault hits
        # bucket 0 only, so chaos-drill counter expectations survive any
        # bucket count (resilience/inject.py wire_schedule).
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        if leaves:
            starts = (_leaf_starts(grads) if offset_starts is None
                      else list(offset_starts))
            sizes = [l.size for l in leaves]
            # the ring is ALWAYS bucketed at the same default cap the
            # overlap taps use (BucketPlan.for_tree): a tree that fits
            # one bucket rings whole — the historical behavior — and a
            # larger tree gets the same per-bucket layout whether the
            # reduction runs post-backward or inside the taps, so
            # overlap on/off is bitwise identical at bucket_elems=None
            # too (not just at an explicit cap)
            buckets = bucket_layout(
                sizes, bucket_elems if bucket_elems is not None
                else _BUCKET_ELEMS)
            out = [None] * len(leaves)
            reports = []
            for b, idxs in enumerate(buckets):
                flat = (leaves[idxs[0]].astype(jnp.float32).reshape(-1)
                        if len(idxs) == 1 else
                        jnp.concatenate([leaves[i].astype(jnp.float32)
                                         .reshape(-1) for i in idxs]))
                # contiguous bucket -> cheap scalar offset_start; a
                # bucket spanning non-adjacent global offsets ships the
                # full per-element offset array instead
                contig = all(starts[i] + sizes[i] == starts[j]
                             for i, j in zip(idxs, idxs[1:]))
                off_kw = (dict(offset_start=int(starts[idxs[0]]))
                          if contig else
                          dict(offsets=jnp.concatenate(
                              [_leaf_offsets(starts[i], leaves[i]).ravel()
                               for i in idxs])))
                red = hierarchical_ring_sum(
                    flat, axis_name, grad_exp, grad_man,
                    use_kahan=use_kahan, key=k_sum, verify=verify,
                    fault=(wire_fault if b == 0 else None),
                    block_scale=block_scale, block_size=block_size,
                    **off_kw)
                if verify:
                    red, rep = red
                    reports.append(rep)
                off = 0
                for i in idxs:
                    out[i] = lax.dynamic_slice_in_dim(red, off, sizes[i]) \
                        .reshape(leaves[i].shape).astype(leaves[i].dtype)
                    off += sizes[i]
            reduced = jax.tree_util.tree_unflatten(treedef, out)
            if verify:
                report = _merge_verify_reports(reports)
        else:
            reduced = grads
            if verify:
                report = _clean_verify_report()
    else:
        # Wire compression: with APS the gathered values were quantized to
        # the (exp, man) value set just above, so the W x gather ships the
        # bit-packed code words — wire_bytes(exp, man) bytes per element —
        # losslessly (bit-identical results; tested).  Without APS the
        # reference gathers RAW fp32 grads (dist_util.py:62-64), so no
        # compression is possible without changing semantics.
        wire = _wire_format(grad_exp, grad_man) if use_aps else None
        if grad_exp == 8 and grad_man == 23 and not use_kahan:
            # fp32 fast path == plain all-reduce: the reference takes the
            # same shortcut at the identity format (dist_util.py:55-59),
            # so XLA-order psum here is reference parity, not a loss.
            with jax.named_scope(scopes.WIRE_COLLECTIVE):
                reduced = jax.tree.map(  # cpd: disable=kahan-ordering
                    lambda g: lax.psum(g, axis_name), grads)
        else:
            reduced = _faithful_quantized_sum(
                grads, axis_name, grad_exp, grad_man, use_kahan,
                bucket_elems=(None if not bucket else
                              bucket_elems if bucket_elems is not None
                              else _BUCKET_ELEMS),
                wire=wire, key=k_sum, starts=offset_starts)

    if use_aps:
        reduced = aps_unscale(reduced, shifts)
    if verify or stats:
        if verify:
            if mode != "ring":
                # psum / all_gather have no custom wire to checksum; the
                # cross-replica agreement digest is the whole verdict
                from .integrity import digest_agree, tree_digest
                agree = digest_agree(tree_digest(reduced), axis_name)
                report = _clean_verify_report()
                report["agree"] = agree
                report["ok"] = agree
        else:
            report = {}
        if stats:
            # SUM the per-rank counts so every replica reports the same
            # cluster-wide verdict (the supervisor's escalation decision
            # must agree across hosts); aps_bad is replicated already
            # (computed from the pmax'd vector)
            report.update({"wire_" + k: lax.psum(v, axis_name)
                           for k, v in prec.items()})
            report["aps_bad"] = aps_bad
        return reduced, report
    return reduced


def _clean_verify_report() -> dict:
    i0, i1 = jnp.zeros([], jnp.int32), jnp.ones([], jnp.int32)
    return {"hop_bad": i0, "gather_bad": i0, "agree": i1, "ok": i1}


def _merge_verify_reports(reports: list) -> dict:
    """Merge per-bucket ring verification reports into one verdict:
    mismatch COUNTS add, agreement ANDs, and ``ok`` is recomputed from
    the merged fields — one corrupt bucket fails the step exactly as a
    corrupt whole-tree ring did."""
    if not reports:
        return _clean_verify_report()
    hop = sum((r["hop_bad"] for r in reports[1:]),
              reports[0]["hop_bad"])
    gather = sum((r["gather_bad"] for r in reports[1:]),
                 reports[0]["gather_bad"])
    agree = reports[0]["agree"]
    for r in reports[1:]:
        agree = jnp.minimum(agree, r["agree"])
    return {"hop_bad": hop, "gather_bad": gather, "agree": agree,
            "ok": ((hop == 0) & (gather == 0)
                   & (agree == 1)).astype(jnp.int32)}


def make_sum_gradients_fn(mesh: Mesh, axis_name: str = "data", **kwargs):
    """Standalone jitted ``stacked_grads -> reduced`` over `mesh.axis_name`.

    Input: pytree whose leaves are stacked per-rank gradients ``(W, *shape)``
    (the multi-controller analog of "each rank holds its own grad").  Output:
    the reduced pytree with leaf shape ``(*shape,)``, replicated.

    This mirrors the reference's usage pattern of an explicit post-backward
    `sum_gradients(model)` call (mix.py:286-291).  Trainers that jit a whole
    train step should instead call `sum_gradients` inline inside their
    shard_map — one trace, no extra dispatch."""
    from ..compat import shard_map

    fn = functools.partial(sum_gradients, axis_name=axis_name, **kwargs)

    def body(stacked):
        local = jax.tree.map(lambda g: g[0], stacked)  # this rank's grad
        return fn(local)

    # Keyed by treedef so jit's trace cache is actually hit — and BOUNDED:
    # a long-lived reducer fed many distinct pytree structures (sweeps,
    # notebooks) must not grow a callable per structure forever.  Eviction
    # only costs a re-trace on the next call with that structure.
    from ..utils.cache import LRUCache
    jitted = LRUCache(maxsize=16)

    def reduced(stacked_grads):
        # the key carries the layout-affecting coordinates alongside the
        # structure: a cached callable traced for one (mode, bucket
        # layout) must never serve another (the PR 5 half-keyed-table
        # bug class, extended to the bucket coordinate) — today they are
        # per-instance constants, but the key is what guards tomorrow
        treedef = (jax.tree.structure(stacked_grads),
                   kwargs.get("mode", "faithful"),
                   kwargs.get("bucket_elems"),
                   kwargs.get("block_scale", False),
                   kwargs.get("block_size", 128))

        def build():
            in_spec = jax.tree.map(lambda _: P(axis_name), stacked_grads)
            out_spec = jax.tree.map(lambda _: P(), stacked_grads)
            return jax.jit(
                shard_map(body, mesh=mesh, in_specs=(in_spec,),
                          out_specs=out_spec, check_vma=False))

        return jitted.get_or_create(treedef, build)(stacked_grads)

    reduced._cache = jitted   # introspectable bound (tests assert on it)
    return reduced
