"""The shared train/eval step — one traced graph per configuration.

This replaces the reference's three copy-pasted training loops
(example/ResNet18/tools/mix.py:224-356, example/DavidNet/utils.py:328-344,
example/ResNet50/main.py:141-212).  Where the reference's step is a Python
loop issuing one CUDA kernel / NCCL op per parameter per micro-batch
(SURVEY.md §3.1 "kernel-launch storm"), here the WHOLE step — micro-batch
scan, local emulated-node reduction, APS, the quantized cross-device
all-reduce, and the optimizer — is a single jitted shard_map program, so XLA
fuses the quantize math into the surrounding elementwise work and schedules
the ICI collectives back-to-back.

Semantics preserved from the reference step (mix.py:224-314):
  * loss divided by world*emulate_node so the distributed SUM equals the
    mean (mix.py:239);
  * optional loss scaling, multiplied into the loss before grad and NOT
    unscaled before the step — faithful to DavidNet/utils.py:332-334, which
    never unscales (default scale 1.0 makes it a no-op); beyond-reference,
    ``loss_scale="dynamic"`` reads the scale from a
    `with_dynamic_loss_scale` optimizer state instead (train/scaling.py:
    GradScaler policy — unscale, skip non-finite steps, halve/double);
  * micro-batches run sequentially (lax.scan), so BN running stats update
    in the same order as the reference's sequential sub-batch loop;
  * the reported loss is the cross-rank all-reduced copy (mix.py:240-242).

Deviation (documented): BN running stats are cross-replica pmean'd at the
end of the step.  The reference keeps per-rank stats and checkpoints
rank-0's (train_util.py:268-271); with jit+shard_map, replicated outputs
must be bitwise-replicated, and averaging is strictly more principled than
"whatever rank 0 saw".
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..compat import shard_map
from ..obs import scopes
from .grads import ReduceOptions, reduced, report_metrics
from .state import TrainState

__all__ = ["cross_entropy_loss", "seg_cross_entropy_loss",
           "seg_loss_with_aux", "make_train_step", "make_eval_step",
           "make_seg_eval_step"]


def _main_logits(out):
    """Models with an auxiliary head return (main, aux); metrics and eval
    use the main logits only (mmseg semantics: aux is train-time loss)."""
    return out[0] if isinstance(out, tuple) else out


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean softmax cross-entropy with integer labels (the criterion of all
    three reference trainers, e.g. mix.py:104)."""
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()


def seg_cross_entropy_loss(ignore_label: int = 255) -> Callable:
    """Per-pixel CE averaged over non-ignored pixels — the segmentation
    criterion of the FCN/Cityscapes config (reference README.md:132-150;
    mmseg's CrossEntropyLoss with ignore_index=255)."""

    def loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
        valid = labels != ignore_label
        safe = jnp.where(valid, labels, 0)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, safe)
        return jnp.sum(ce * valid) / jnp.maximum(jnp.sum(valid), 1)

    return loss


def seg_loss_with_aux(ignore_label: int = 255,
                      aux_weight: float = 0.4) -> Callable:
    """Main + aux_weight * auxiliary segmentation loss for models returning
    (main_logits, aux_logits) — mmseg's fcn_r50-d8 trains the aux FCN head
    on layer3 features at loss weight 0.4 (reference README.md:132-150)."""
    base = seg_cross_entropy_loss(ignore_label)

    def loss(out, labels: jnp.ndarray) -> jnp.ndarray:
        main, aux = out
        return base(main, labels) + aux_weight * base(aux, labels)

    return loss


def make_train_step(model, tx: optax.GradientTransformation, mesh: Mesh,
                    *, axis_name: str = "dp", emulate_node: int = 1,
                    use_aps: bool = False, grad_exp: int = 8,
                    grad_man: int = 23, use_kahan: bool = False,
                    mode: str = "faithful", loss_scale: float = 1.0,
                    grad_rounding: str = "nearest", grad_seed: int = 0,
                    loss_fn: Callable = cross_entropy_loss,
                    rng_keys: tuple = (), rng_seed: int = 0,
                    ignore_label: Optional[int] = None,
                    donate: bool = True,
                    update_fn: Optional[Callable] = None,
                    opt_state_spec: Optional[Any] = None,
                    reduce_in_update: bool = False,
                    params_spec: Optional[Any] = None,
                    unpack_params: Optional[Callable] = None,
                    tap_reduce: Optional[Callable] = None,
                    verify_reduce: bool = False,
                    wire_fault_plan: Optional[tuple] = None,
                    quant_stats: bool = False,
                    sat_fault_plan: Optional[Any] = None,
                    overlap_reduce: bool = False,
                    bucket_elems: Optional[int] = None,
                    block_scale: bool = False,
                    block_size: int = 128):
    """Build the jitted ``(state, images, labels) -> (state, metrics)`` step.

    images: (global_batch * emulate_node, H, W, C) sharded over `axis_name`;
    each device's local slice is split into `emulate_node` sequential
    micro-batches (the reference's virtual-node emulation, mix.py:224-285).
    Returned metrics: {'loss': all-reduced mean loss, 'accuracy': top-1 over
    the global batch, 'lr'-free — schedule owns lr}.

    reduce_in_update=True (requires update_fn) skips the step's own
    `sum_gradients` and hands update_fn the rank-LOCAL post-emulate
    gradients — for updaters that fold the collective into the update,
    e.g. ZeRO-2's sharded faithful reduce-scatter (parallel/zero.py).

    params_spec / unpack_params support ZeRO-3 parameter sharding:
    `params_spec` is the PartitionSpec of TrainState.params (default
    replicated), and `unpack_params(stored_params, axis_name)` maps the
    stored layout to the model's param pytree inside shard_map (e.g. the
    flat-shard all_gather + unflatten of parallel/zero.py `_Zero3`);
    update_fn then returns params back in the STORED layout.

    use_aps ... block_size, the fifteen keywords of the reduction, are
    `train.grads.ReduceOptions`' fields and are described there, once.
    What is this builder's own about them:

    overlap_reduce keeps the sequential batch-statistics order of the
    scan; the statistics agree with the monolith's to the last ulp only
    (training-mode BN normalizes by the batch's own, so gradients never
    see the drift).  It composes with reduce_in_update where the updater
    provides the ``tap_reduce`` hook (ZeRO-2's
    `zero2_sgd(...).mesh_layout` wires it): the taps run the updater's
    per-bucket all_to_all reduce-scatter inside the backward and
    `update_fn` consumes the extracted bucket shards
    (``pre_sharded=True``), bitwise identical to the post-backward
    reduce_in_update monolith at a fixed bucket layout.

    block_scale needs mode="ring", EXCEPT with reduce_in_update, where
    the pair is forwarded to the updater and ZeRO-2's faithful
    all_to_all carries the blocked wire instead (ISSUE 12 leg 1).
    """
    opts = ReduceOptions(
        use_aps=use_aps, grad_exp=grad_exp, grad_man=grad_man,
        use_kahan=use_kahan, mode=mode, grad_rounding=grad_rounding,
        grad_seed=grad_seed, verify_reduce=verify_reduce,
        wire_fault_plan=wire_fault_plan, quant_stats=quant_stats,
        sat_fault_plan=sat_fault_plan, overlap_reduce=overlap_reduce,
        bucket_elems=bucket_elems, block_scale=block_scale,
        block_size=block_size).check(reduce=not reduce_in_update)
    dynamic_scale = loss_scale == "dynamic"
    if dynamic_scale and update_fn is not None:
        raise ValueError("loss_scale='dynamic' requires the default optax "
                         "update path (the wrapper owns unscale+skip); "
                         "custom update_fn steppers must manage scaling "
                         "themselves")
    if not dynamic_scale:
        loss_scale = float(loss_scale)
    if reduce_in_update and update_fn is None:
        raise ValueError("reduce_in_update=True requires update_fn")
    if unpack_params is not None and update_fn is None:
        raise ValueError("unpack_params requires update_fn (the default "
                         "optax update assumes stored params == model "
                         "params)")
    if params_spec is not None and unpack_params is None:
        raise ValueError("params_spec (sharded stored params) requires "
                         "unpack_params to rebuild the model pytree "
                         "inside the step")
    if verify_reduce and reduce_in_update:
        raise ValueError("verify_reduce=True needs the step's own "
                         "sum_gradients call; reduce_in_update hands the "
                         "collective to the updater (ZeRO-2/3), which "
                         "does not thread a verification report")
    if quant_stats and reduce_in_update:
        raise ValueError("quant_stats=True needs the step's own "
                         "sum_gradients call; reduce_in_update hands the "
                         "collective to the updater (ZeRO-2/3), which "
                         "does not thread a telemetry report")
    if tap_reduce is not None and not reduce_in_update:
        raise ValueError("tap_reduce is the ZeRO-2 overlap hook — it "
                         "only makes sense with reduce_in_update=True")
    if overlap_reduce and reduce_in_update and tap_reduce is None:
        raise ValueError(
            "overlap_reduce=True with reduce_in_update needs the "
            "updater's tap_reduce hook (zero2_sgd's mesh_layout wires "
            "it); ZeRO-3 and other custom updaters without one own the "
            "whole post-backward collective — run without "
            "overlap_reduce")

    def micro_rngs(step, micro_idx):
        """Per-micro-step stream rngs (dropout etc.), deterministic in
        (rng_seed, replica, global step, micro index) — the replica fold
        keeps dropout masks decorrelated across data-parallel shards
        (one rng stream per rank, as torch DDP gives)."""
        if not rng_keys:
            return {}
        base = jax.random.fold_in(jax.random.PRNGKey(rng_seed),
                                  step * emulate_node + micro_idx)
        base = jax.random.fold_in(
            base, lax.axis_index(axis_name).astype(jnp.int32))
        return {k: jax.random.fold_in(base, i)
                for i, k in enumerate(rng_keys)}

    def _count_hits(logits, y):
        hit = jnp.argmax(_main_logits(logits), -1) == y
        if ignore_label is not None:
            valid = y != ignore_label
            return jnp.sum(hit & valid), jnp.sum(valid)
        return jnp.sum(hit), jnp.asarray(y.size)

    def step_fn(state: TrainState, images, labels):
        world = lax.psum(jnp.float32(1.0), axis_name)
        model_params = (unpack_params(state.params, axis_name)
                        if unpack_params is not None else state.params)
        from .scaling import DynamicScaleState, current_scale
        if dynamic_scale:
            scale = current_scale(state.opt_state)
        else:
            # symmetric to current_scale's TypeError: a wrapped optimizer
            # with a static loss_scale would silently divide every update
            # by the (growing) scale.  The search covers the WHOLE
            # opt_state pytree, not just the outermost node — e.g.
            # optax.chain(clip, with_dynamic_loss_scale(tx)) nests the
            # wrapper's state one level down.
            def _is_dyn(n):
                return isinstance(n, DynamicScaleState)
            if any(map(_is_dyn, jax.tree.leaves(
                    state.opt_state, is_leaf=_is_dyn))):
                raise ValueError(
                    "optimizer is wrapped with with_dynamic_loss_scale but "
                    "loss_scale is static; pass loss_scale='dynamic' to "
                    "make_train_step")
            scale = jnp.float32(loss_scale)

        def loss_of(p, stats, xy, micro_idx):
            """One micro-batch's loss: the carry is the batch statistics,
            the aux its unscaled loss and hit counts."""
            x, y = xy
            rngs = micro_rngs(state.step, micro_idx)
            variables = {"params": p}
            kwargs = {"rngs": rngs} if rngs else {}
            if jax.tree.leaves(stats):
                variables["batch_stats"] = stats
                logits, mut = model.apply(variables, x, train=True,
                                          mutable=["batch_stats"], **kwargs)
                stats = mut["batch_stats"]
            else:
                logits = model.apply(variables, x, train=True, **kwargs)
            loss = loss_fn(logits, y) / (world * emulate_node)  # mix.py:239
            return loss * scale, (stats, (loss, *_count_hits(logits, y)))

        n = emulate_node
        if images.shape[0] < n or images.shape[0] % n:
            # a 0-sample micro-batch silently yields NaN losses (mean over
            # an empty batch); fail at trace time with the actual geometry
            raise ValueError(
                f"per-device batch {images.shape[0]} must be a positive "
                f"multiple of emulate_node={n} (global batch = "
                f"devices * per-device batch; each device slice is split "
                f"into emulate_node sequential micro-batches)")
        out = reduced(
            loss_of, model_params, (images, labels), n=n,
            carry=state.batch_stats, step=state.step, axis_dp=axis_name,
            opts=opts, tap_reduce=tap_reduce, reduce=not reduce_in_update)
        new_stats = out.carry
        loss, correct, counted = (a.sum() for a in out.aux)

        with jax.named_scope(scopes.OPTIMIZER):
            if update_fn is not None:
                # custom update (e.g. parallel/zero.py ZeRO: shard-local
                # optimizer math); must return params in the STORED layout
                # (full replicated by default; the rank's shard when
                # params_spec/unpack_params are in play) and the (possibly
                # sharded) new opt state.
                # With reduce_in_update the stage's `update_kw` carries the
                # step's precision settings and the collective's SR key, so
                # the updater's collective cannot drift from the
                # emulate-node quantization (parallel/zero.py); after
                # ZeRO-2's taps it says the shards are reduced already.
                new_params, new_opt = update_fn(out.grads, state, axis_name,
                                                **out.update_kw)
            else:
                updates, new_opt = tx.update(out.grads, state.opt_state,
                                             state.params)
                new_params = optax.apply_updates(state.params, updates)
        with jax.named_scope(scopes.METRICS):
            new_stats = jax.tree.map(lambda s: lax.pmean(s, axis_name),
                                     new_stats)

        new_state = TrainState(step=state.step + 1, params=new_params,
                               batch_stats=new_stats, opt_state=new_opt)
        # resilience counters (guard skip/overflow/spike totals, injected
        # fault count) ride along as replicated scalars whenever the
        # optimizer is wrapped with resilience.with_grad_guard /
        # with_fault_injection; {} otherwise, so the metric dict shape is
        # unchanged for unguarded runs.
        from ..resilience.guard import guard_metrics
        with jax.named_scope(scopes.METRICS):
            metrics = {
                **guard_metrics(new_opt),
                # loss is the per-rank sum of micro losses (already /world/n);
                # psum across ranks gives the global mean (mix.py:240-242).
                # (`loss` aux output is the UNSCALED per-micro loss, so no
                # scale division is needed for either static or dynamic.)
                "loss": lax.psum(loss, axis_name),
                # element counts (not shape[0]) so dense label maps (FCN pixel
                # accuracy, minus ignore_label pixels) and flat class labels
                # share one metric definition.
                "accuracy": lax.psum(correct.astype(jnp.float32), axis_name)
                            / jnp.maximum(
                                lax.psum(counted.astype(jnp.float32),
                                         axis_name),
                                1.0),
            }
        metrics.update(report_metrics(out.report, opts))
        return new_state, metrics

    if opt_state_spec is None and params_spec is None:
        state_spec: Any = P()   # fully replicated state
    else:
        state_spec = TrainState(step=P(), params=params_spec or P(),
                                batch_stats=P(),
                                opt_state=opt_state_spec
                                if opt_state_spec is not None else P())
    data_spec = P(axis_name)    # batch-sharded
    shard_fn = shard_map(
        step_fn, mesh=mesh,
        in_specs=(state_spec, data_spec, data_spec),
        out_specs=(state_spec, P()),
        check_vma=False)
    return jax.jit(shard_fn, donate_argnums=(0,) if donate else ())


def make_multi_train_step(model, tx: optax.GradientTransformation,
                          mesh: Mesh, k: int, *, axis_name: str = "dp",
                          donate: bool = True, **kw):
    """K train steps fused into ONE executable via `lax.scan`.

    ``(state, images (k, B, ...), labels (k, B, ...)) -> (state, metrics)``
    where metrics are the LAST step's.  Semantically identical to calling
    the single step k times; operationally it amortizes per-dispatch
    overhead (the host->device launch) over k steps — the idiomatic TPU
    training loop shape.  Batches for all k steps must be resident up
    front.
    """
    # the inner jit inlines when traced inside the scan body
    single = make_train_step(model, tx, mesh, axis_name=axis_name,
                             donate=False, **kw)

    def multi(state, xs, ys):
        def body(s, xy):
            s, m = single(s, xy[0], xy[1])
            return s, m

        state, ms = jax.lax.scan(body, state, (xs, ys))
        last = jax.tree.map(lambda a: a[-1], ms)
        return state, last

    return jax.jit(multi, donate_argnums=(0,) if donate else ())


def make_eval_step(model, mesh: Mesh, *, axis_name: str = "dp",
                   loss_fn: Callable = cross_entropy_loss):
    """Jitted ``(state, images, labels) -> metrics`` (validate() parity,
    mix.py:359-425: all-reduced loss sum + top-1/top-5 counts)."""

    def step_fn(state: TrainState, images, labels):
        variables = {"params": state.params}
        if jax.tree.leaves(state.batch_stats):
            variables["batch_stats"] = state.batch_stats
        logits = _main_logits(model.apply(variables, images, train=False))
        loss = loss_fn(logits, labels)
        top1 = jnp.sum(jnp.argmax(logits, -1) == labels)
        k = min(5, logits.shape[-1])
        topk = jnp.sum(jnp.any(
            lax.top_k(logits, k)[1] == labels[:, None], axis=-1))
        n = jnp.float32(labels.shape[0])
        return {
            "loss": lax.psum(loss * n, axis_name) / lax.psum(n, axis_name),
            "top1": lax.psum(top1.astype(jnp.float32), axis_name)
                    / lax.psum(n, axis_name),
            "top5": lax.psum(topk.astype(jnp.float32), axis_name)
                    / lax.psum(n, axis_name),
        }

    shard_fn = shard_map(
        step_fn, mesh=mesh,
        in_specs=(P(), P(axis_name), P(axis_name)),
        out_specs=P(),
        check_vma=False)
    return jax.jit(shard_fn)


def make_seg_eval_step(model, mesh: Mesh, num_classes: int, *,
                       axis_name: str = "dp", ignore_label: int = 255):
    """Jitted segmentation eval: ``(state, images, labels) -> metrics``.

    The mmseg-style periodic evaluation the reference's FCN workload
    relies on (its mmcv runner's EvalHook; README.md:132-150).  Returns
    per-batch sums so the caller can stream over a whole split:
      loss_sum / n_pix  — ignored pixels excluded;
      correct           — pixel-accuracy numerator;
      inter / union     — per-class (num_classes,) intersection and union
                          counts; mIoU = mean over classes with union>0
                          after accumulating all batches (the standard
                          Cityscapes metric over the 19 train classes).
    """

    def step_fn(state: TrainState, images, labels):
        variables = {"params": state.params}
        if jax.tree.leaves(state.batch_stats):
            variables["batch_stats"] = state.batch_stats
        logits = _main_logits(model.apply(variables, images, train=False))
        valid = labels != ignore_label
        safe = jnp.where(valid, labels, 0)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), safe)   # same op as the train loss
        loss_sum = jnp.sum(ce * valid)
        pred = jnp.argmax(logits, -1)
        correct = jnp.sum((pred == labels) & valid)
        cls = jnp.arange(num_classes)
        pred_m = (pred[..., None] == cls) & valid[..., None]
        lab_m = (safe[..., None] == cls) & valid[..., None]
        inter = jnp.sum(pred_m & lab_m, axis=tuple(range(labels.ndim)))
        union = jnp.sum(pred_m | lab_m, axis=tuple(range(labels.ndim)))
        f = jnp.float32
        return {
            "loss_sum": lax.psum(f(loss_sum), axis_name),
            "n_pix": lax.psum(f(jnp.sum(valid)), axis_name),
            "correct": lax.psum(f(correct), axis_name),
            "inter": lax.psum(inter.astype(jnp.float32), axis_name),
            "union": lax.psum(union.astype(jnp.float32), axis_name),
        }

    shard_fn = shard_map(
        step_fn, mesh=mesh,
        in_specs=(P(), P(axis_name), P(axis_name)),
        out_specs=P(),
        check_vma=False)
    return jax.jit(shard_fn)


def ir_programs(reg):
    """Program-contract declarations (analysis/ir/registry.py): the
    vision step builder traced at representative ladder coordinates.

    * overlap twins — the ring step with and without ``overlap_reduce``
      (and a ZeRO-2 tap-reduce pair) claim bitwise parity
      (tests/test_overlap.py); `ir-schedule` pins their collective
      multisets identical and `ir-overlap` their interleaving verdicts.
    * the ``step.ladder`` retrace family — the SAME perturbed config
      coordinates the CLIs' StepTable would hold, each declared with
      its REAL `ladder_step_key`; `ir-retrace` asserts distinct traced
      programs never share a key (the PR 5 half-keyed bug, verified
      dynamically rather than by AST pattern).
    * every member is bitwise-gated: the step wraps the whole
      reduce/APS pipeline, so one stray `exp2` anywhere under it fails
      `ir-bitwise` (the PR 12 class).

    The LR schedule is a constant on purpose: `warmup_step_decay`'s
    ``gamma ** k`` lowers to the unstable `pow` primitive, and the lr
    is not the contract under test."""
    from types import SimpleNamespace

    from ..models.tiny import tiny_cnn
    from ..resilience.precision import ladder_step_key
    from .optim import make_optimizer
    from .state import create_train_state

    W, BUCKET = 8, 100
    deps = ("cpd_tpu.train.step", "cpd_tpu.train.grads",
            "cpd_tpu.parallel.dist",
            "cpd_tpu.parallel.ring", "cpd_tpu.parallel.overlap",
            "cpd_tpu.parallel.aps", "cpd_tpu.parallel.emulate",
            "cpd_tpu.parallel.zero", "cpd_tpu.quant.numerics",
            "cpd_tpu.models.tiny")

    def _key(mode, fmt, overlap=None, block=None):
        return ladder_step_key(transport=SimpleNamespace(mode=mode),
                               precision=SimpleNamespace(fmt=fmt),
                               overlap=overlap, block=block)

    def _vision(mode, fmt, overlap=False, block=None, zero2=False):
        def build():
            from ..parallel.mesh import data_parallel_mesh
            mesh = data_parallel_mesh()
            model = tiny_cnn(num_classes=4, width=4)
            tx = make_optimizer("sgd", lambda step: 0.1, momentum=0.9)
            def fresh_state():
                return create_train_state(model, tx,
                                          jnp.zeros((2, 8, 8, 3)),
                                          jax.random.PRNGKey(0))

            kw = dict(use_aps=True, grad_exp=fmt[0], grad_man=fmt[1],
                      mode=mode, grad_rounding="stochastic",
                      grad_seed=5, bucket_elems=BUCKET, donate=False,
                      overlap_reduce=overlap,
                      block_scale=block is not None,
                      block_size=block if block is not None else 128)
            if zero2:
                from ..parallel.zero import zero2_sgd
                z = zero2_sgd(lambda step: 0.1, W, bucket_elems=BUCKET)

                def mk():
                    st = fresh_state()
                    return TrainState(step=st.step, params=st.params,
                                      batch_stats=st.batch_stats,
                                      opt_state=z.init(st.params))

                state = jax.eval_shape(mk)
                kw.update(mode="faithful", grad_rounding="nearest",
                          bucket_elems=BUCKET if overlap else None,
                          update_fn=z.update_fn,
                          opt_state_spec=z.state_spec(),
                          reduce_in_update=True,
                          block_scale=False, block_size=128)
                if overlap:
                    kw["tap_reduce"] = z.make_tap_reduce
            else:
                state = jax.eval_shape(fresh_state)
            step = make_train_step(model, tx, mesh, **kw)
            abstract = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(jnp.shape(l),
                                               jnp.result_type(l)),
                state)
            x = jax.ShapeDtypeStruct((16, 8, 8, 3), jnp.float32)
            y = jax.ShapeDtypeStruct((16,), jnp.int32)
            return step, (abstract, x, y)
        return build

    reg.declare(
        "step.ring[e5m2,sr,aps]", _vision("ring", (5, 2)),
        deps=deps, axis_sizes={"dp": W}, bitwise=True,
        twin="step.ring-overlap", overlap=False,
        retrace_group="step.ladder",
        retrace_key=_key("ring", (5, 2), overlap=(False, BUCKET)))
    reg.declare(
        "step.ring[e5m2,sr,aps]+overlap", _vision("ring", (5, 2),
                                                  overlap=True),
        deps=deps, axis_sizes={"dp": W}, bitwise=True,
        twin="step.ring-overlap", overlap=True,
        retrace_group="step.ladder",
        retrace_key=_key("ring", (5, 2), overlap=(True, BUCKET)))
    reg.declare(
        "step.faithful[e5m2,sr,aps]", _vision("faithful", (5, 2)),
        deps=deps, axis_sizes={"dp": W}, bitwise=True,
        retrace_group="step.ladder",
        retrace_key=_key("faithful", (5, 2), overlap=(False, BUCKET)))
    reg.declare(
        "step.ring[e5m7,sr,aps]", _vision("ring", (5, 7)),
        deps=deps, axis_sizes={"dp": W}, bitwise=True,
        retrace_group="step.ladder",
        retrace_key=_key("ring", (5, 7), overlap=(False, BUCKET)))
    reg.declare(
        "step.ring[blocked-e4m3,b32,sr,aps]",
        _vision("ring", (4, 3), block=32),
        deps=deps, axis_sizes={"dp": W}, bitwise=True,
        retrace_group="step.ladder",
        retrace_key=_key("ring", (4, 3), overlap=(False, BUCKET),
                         block=(True, 32)))
    reg.declare(
        "step.zero2[aps,e5m2]", _vision("ring", (5, 2), zero2=True),
        deps=deps, axis_sizes={"dp": W}, bitwise=True,
        twin="step.zero2-overlap", overlap=False)
    reg.declare(
        "step.zero2[aps,e5m2]+overlap",
        _vision("ring", (5, 2), overlap=True, zero2=True),
        deps=deps, axis_sizes={"dp": W}, bitwise=True,
        twin="step.zero2-overlap", overlap=True)
