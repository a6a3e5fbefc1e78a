"""Transformer-LM trainer over a dp x sp x tp mesh — the long-context /
multi-axis entry point.

No reference counterpart (the reference is CNN-only, SURVEY.md §5); this
CLI demonstrates the framework's full parallelism surface in one command:
ring-attention sequence parallelism, Megatron tensor parallelism, and the
reference's quantized APS gradient all-reduce on the data axis
(--use_APS/--grad_exp/--grad_man/--use_kahan/--emulate_node, same flags as
every other trainer).

    python examples/lm/train.py --dp 2 --sp 2 --tp 2 --seq-len 2048 \
        --use_APS --grad_exp 5 --grad_man 2
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from cpd_tpu.obs.timing import now  # noqa: E402  (the one clock; jax-free)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="cpd_tpu transformer LM")
    p.add_argument("--dp", default=0, type=int,
                   help="data-parallel size (0 = all remaining devices)")
    p.add_argument("--sp", default=1, type=int, help="sequence-parallel")
    p.add_argument("--sp-mode", default="ring",
                   choices=["ring", "ulysses"],
                   help="sequence-parallel attention: ring (ppermute K/V) "
                        "or ulysses (all_to_all heads<->sequence; needs "
                        "local heads divisible by --sp)")
    p.add_argument("--tp", default=1, type=int, help="tensor-parallel")
    p.add_argument("--pp", default=1, type=int,
                   help="pipeline-parallel (GPipe; composes with --tp, "
                   "excludes sp/moe)")
    p.add_argument("--n-microbatches", default=4, type=int,
                   help="pipeline microbatches per step (with --pp)")
    p.add_argument("--vocab-pp", action="store_true",
                   help="shard the tied embed/head table over pp "
                   "(vocab-parallel lookup/logits/CE; with --pp)")
    p.add_argument("--moe", action="store_true",
                   help="Switch-style MoE feed-forward (excludes sp/tp/pp)")
    p.add_argument("--ep", default=1, type=int,
                   help="expert-parallel size (with --moe)")
    p.add_argument("--n-experts", default=4, type=int)
    p.add_argument("--vocab-size", default=256, type=int)
    p.add_argument("--d-model", default=256, type=int)
    p.add_argument("--n-layers", default=4, type=int)
    p.add_argument("--n-heads", default=8, type=int)
    p.add_argument("--n-kv-heads", default=None, type=int,
                   help="GQA: fewer K/V heads than query heads (must "
                        "divide --n-heads; default = MHA)")
    p.add_argument("--seq-len", default=256, type=int)
    p.add_argument("--batch-size", default=8, type=int,
                   help="sequences per dp rank per micro-step")
    p.add_argument("--max-iter", default=200, type=int)
    p.add_argument("--base-lr", default=0.01, type=float)
    p.add_argument("--lr-schedule", default="constant",
                   choices=["constant", "cosine"],
                   help="after warmup: constant (default) or cosine decay "
                        "to 0 at --max-iter")
    p.add_argument("--optimizer", default="sgd",
                   choices=["sgd", "nesterov", "adamw"],
                   help="elementwise optimizers only (shard-local update "
                        "under tp; LARS is guarded off in train/lm.py)")
    p.add_argument("--remat", action="store_true",
                   help="jax.checkpoint each transformer block: recompute "
                        "activations in backward instead of storing them "
                        "(the HBM<->FLOPs trade for deep/long-context "
                        "runs)")
    p.add_argument("--scan-layers", action="store_true",
                   help="nn.scan the block stack: compile the layer body "
                        "once regardless of depth (params gain a leading "
                        "layer axis; checkpoint layout differs from the "
                        "unrolled form)")
    p.add_argument("--warmup-iters", default=20, type=int)
    p.add_argument("--print-freq", default=10, type=int)
    p.add_argument("--save-path", default="lm_ckpt")
    p.add_argument("--val-freq", default=100, type=int)
    p.add_argument("--ckpt-freq", default=500, type=int)
    # the reference-parity precision flags
    p.add_argument("--grad_exp", default=8, type=int)
    p.add_argument("--grad_man", default=23, type=int)
    p.add_argument("--grad-rounding", default="nearest",
                   choices=["nearest", "stochastic"],
                   help="rounding of the gradient-pipeline casts; "
                        "stochastic = unbiased SR (dp path only)")
    p.add_argument("--grad-seed", default=0, type=int)
    p.add_argument("--use_APS", action="store_true")
    p.add_argument("--use_kahan", action="store_true")
    p.add_argument("--emulate_node", default=1, type=int)
    p.add_argument("--mode", default="faithful",
                   choices=["faithful", "fast", "ring"])
    p.add_argument("--dist", action="store_true")
    p.add_argument("--tensorboard", action="store_true",
                   help="also write TensorBoard event files next to the "
                        "JSONL scalars (reference mix.py:16,168-171)")
    p.add_argument("--ffn-exp", default=8, type=int,
                   help="MLP GEMM accumulator exponent bits; when "
                        "(--ffn-exp, --ffn-man) != (8, 23) the blocks' "
                        "wi/wo_mlp run the reference quantized GEMM "
                        "recipe")
    p.add_argument("--ffn-man", default=23, type=int)
    p.add_argument("--ffn-mode", default="faithful",
                   choices=["faithful", "fast"],
                   help="faithful = ordered Kahan accumulation (bit-exact "
                        "reference emulation, the API default); fast = "
                        "cast-and-dot")
    p.add_argument("--attn-impl", default="xla",
                   choices=["xla", "flash", "chunked"],
                   help="flash = Pallas flash-attention kernels, O(T) "
                        "memory, non-decode (MHA via the stock TPU "
                        "kernel, GQA via the in-repo GQA-native kernel); "
                        "chunked = pure-XLA online-softmax K/V-block "
                        "scan (flash's memory shape on any backend, "
                        "GQA-native)")
    p.add_argument("--bf16", action="store_true",
                   help="bf16 compute dtype (fp32 master params; the "
                        "MXU-native precision — --half analog of the "
                        "DavidNet trainer)")
    p.add_argument("--label-smoothing", default=0.0, type=float,
                   help="mix one-hot targets with uniform mass in the "
                        "training loss (default dp/sp/tp path)")
    p.add_argument("--dropout", default=0.0, type=float,
                   help="residual-branch dropout rate (train only; "
                        "default dp/sp/tp path)")
    p.add_argument("--sample", default=0, type=int,
                   help="after training, decode this many tokens from a "
                        "data prompt (KV-cache generate; default dp/sp/tp "
                        "path only — pp/moe modules have no decode mode)")
    p.add_argument("--sample-temperature", default=0.0, type=float,
                   help="0 = greedy argmax; >0 samples softmax(l/T)")
    p.add_argument("--sample-top-k", default=None, type=int,
                   help="restrict sampling to the k best tokens "
                        "(needs --sample-temperature > 0)")
    p.add_argument("--sample-top-p", default=None, type=float,
                   help="nucleus sampling mass in (0,1] "
                        "(needs --sample-temperature > 0)")
    p.add_argument("--sample-seed", default=0, type=int,
                   help="rng seed for temperature sampling")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace of a few steps here")
    p.add_argument("--export-torch", default=None, metavar="PATH",
                   help="after training, write a torch state_dict .pth "
                        "of the LM (cpd_tpu.interop.torch_lm; default "
                        "dp/sp/tp path only — pp/moe layouts differ)")
    from cpd_tpu.utils.config import (add_obs_flags,
                                      add_resilience_flags,
                                      add_transport_flags)
    add_resilience_flags(p)       # --fault-plan / guard / watchdog / rollback
    add_transport_flags(p)        # --overlap-reduce / --bucket-elems
    add_obs_flags(p)              # --obs-dir / --obs-flight
    return p


def main(argv=None) -> dict:
    args = build_parser().parse_args(argv)

    import jax
    import jax.numpy as jnp

    from cpd_tpu.data.lm_data import SyntheticText
    from cpd_tpu.models import transformer_lm
    from cpd_tpu.parallel.dist import dist_init
    from cpd_tpu.parallel.mesh import make_mesh
    from cpd_tpu.train import (create_train_state, make_lm_train_step,
                               make_optimizer, warmup_step_decay)
    from cpd_tpu.train.lm import make_lm_eval_step
    from cpd_tpu.utils import ProgressPrinter, ScalarWriter, StepProfiler

    rank, world = dist_init() if args.dist else (0, 1)
    # after dist_init: it consults the resolved backend
    from cpd_tpu.utils import enable_compile_cache
    enable_compile_cache()
    # sampling-flag validation BEFORE training: a bad combination must not
    # surface as a crash after the whole run completed
    if args.sample_temperature == 0 and (args.sample_top_k is not None
                                         or args.sample_top_p is not None):
        raise ValueError("--sample-top-k/--sample-top-p require "
                         "--sample-temperature > 0")
    if args.sample_top_k is not None and args.sample_top_k < 1:
        raise ValueError("--sample-top-k must be >= 1")
    if args.sample_top_p is not None and not 0.0 < args.sample_top_p <= 1.0:
        raise ValueError("--sample-top-p must be in (0, 1]")
    if args.moe and (args.sp > 1 or args.tp > 1):
        raise ValueError("--moe does not compose with sp/tp here")
    if args.pp > 1 and args.sp > 1:
        raise ValueError("--pp does not compose with sp here (ring/"
                         "ulysses need the sequence axis the pipeline "
                         "streams microbatches over)")
    if args.vocab_pp and args.pp <= 1:
        raise ValueError("--vocab-pp needs --pp > 1")
    if args.export_torch and (args.pp > 1 or args.moe):
        raise ValueError("--export-torch supports the default dp/sp/tp "
                         "path only (pp/moe param layouts differ)")
    if args.pp > 1 and args.moe:
        raise ValueError("--pp and --moe are mutually exclusive")
    if (args.pp > 1 or args.moe) and args.emulate_node != 1:
        raise ValueError("--emulate_node is only supported on the "
                         "default dp/sp/tp path")
    if (args.pp > 1 or args.moe) and args.sample > 0:
        raise ValueError("--sample needs the default dp/sp/tp path "
                         "(pp/moe modules have no decode mode)")
    if (args.pp > 1 or args.moe) and (args.remat or args.scan_layers
                                      or args.n_kv_heads is not None
                                      or args.label_smoothing
                                      or args.dropout):
        raise ValueError("--remat/--scan-layers/--n-kv-heads/"
                         "--label-smoothing/--dropout are wired to the "
                         "default dp/sp/tp path only")
    if args.n_kv_heads is not None:
        if args.n_kv_heads < 1:
            raise ValueError(f"n-kv-heads must be >= 1, got "
                             f"{args.n_kv_heads}")
        if args.n_heads % args.n_kv_heads:
            raise ValueError(f"n-heads {args.n_heads} not divisible by "
                             f"n-kv-heads {args.n_kv_heads}")
        if args.n_kv_heads % args.tp:
            raise ValueError(f"n-kv-heads {args.n_kv_heads} not divisible "
                             f"by tp={args.tp}")
    if args.scan_layers and args.sample > 0:
        raise ValueError("--sample (KV-cache decode) does not compose "
                         "with --scan-layers")
    mesh = make_mesh(dp=args.dp, sp=args.sp, tp=args.tp, pp=args.pp,
                     ep=args.ep if args.moe else 1)
    dp = mesh.shape["dp"]

    if args.seq_len % args.sp:
        raise ValueError(f"seq-len {args.seq_len} not divisible by sp={args.sp}")
    if args.n_heads % args.tp:
        raise ValueError(f"n-heads {args.n_heads} not divisible by tp={args.tp}")
    if args.d_model % args.n_heads:
        raise ValueError(f"d-model {args.d_model} not divisible by "
                         f"n-heads {args.n_heads}")
    if (args.d_model // args.n_heads) % 2:
        raise ValueError(f"head dim {args.d_model // args.n_heads} must be "
                         "even (RoPE splits it in half)")

    model_kw = dict(vocab_size=args.vocab_size, d_model=args.d_model,
                    n_layers=args.n_layers, n_heads=args.n_heads,
                    dtype=jnp.bfloat16 if args.bf16 else jnp.float32)
    if args.attn_impl != "xla":
        if args.pp > 1 or args.moe:
            raise ValueError("--attn-impl applies to the default "
                             "dp/sp/tp TransformerLM path only")
        # GQA (--n-kv-heads) + flash is supported EVERYWHERE since the
        # round-5 GQA-native Pallas kernel (ops/flash_gqa.py): plain,
        # ulysses (unexpanded through the all_to_all), decode excluded
        # by the decode path's own gating.  chunked is GQA-native too.
        model_kw.update(attn_impl=args.attn_impl)
    if (args.ffn_exp, args.ffn_man) != (8, 23):
        if args.pp > 1 or args.moe:
            raise ValueError("--ffn-exp/--ffn-man apply to the default "
                             "dp/sp/tp TransformerLM path only")
        model_kw.update(ffn_exp=args.ffn_exp, ffn_man=args.ffn_man,
                        ffn_mode=args.ffn_mode)
    if args.lr_schedule == "cosine":
        from cpd_tpu.train import warmup_cosine
        schedule = warmup_cosine(args.base_lr, args.warmup_iters,
                                 args.max_iter)
    else:
        schedule = warmup_step_decay(args.base_lr, args.warmup_iters,
                                     [args.max_iter * 2], warmup_from=0.0)
    tx = make_optimizer(args.optimizer, schedule, momentum=0.9)
    # resilience stack (docs/RESILIENCE.md): gradient faults + guard are
    # optax wrappers, so they ride inside the jitted step on every path
    # (dp/sp/tp, pp, moe); host faults/watchdog/sentinel wrap the loop.
    from cpd_tpu.resilience import ladder_step_key
    from cpd_tpu.utils.config import build_resilience
    res = build_resilience(args, n_steps=args.max_iter, rank=rank,
                           world=dp)
    esup = res["elastic"]
    if esup is not None:
        # the elastic ladder re-layouts the DATA axis at runtime; the
        # other axes' shardings (and the ladder step tables, which
        # compile against the full-world mesh) don't re-shape that way
        if args.pp > 1 or args.moe or args.sp > 1 or args.tp > 1:
            raise SystemExit("--elastic is wired to the plain dp path "
                             "only (shrinking a sp/tp/pp/moe mesh is "
                             "not a data-axis re-layout)")
        if res["verify"] or res["precision"] is not None:
            raise SystemExit("--elastic does not compose with "
                             "--verify-reduce/--precision-ladder here "
                             "(their step tables compile against the "
                             "full-world mesh; use tools/bench_elastic "
                             "or run_elastic for the composed drills)")
    if res["verify"] and (args.pp > 1 or args.moe):
        raise SystemExit("--verify-reduce is wired to the default "
                         "dp/sp/tp path only (the pp/moe steppers do "
                         "not thread a verification report)")
    if (res["quant_stats"] or res["sat_plan"] is not None) \
            and (args.pp > 1 or args.moe):
        raise SystemExit("--precision-ladder/--quant-telemetry and "
                         "sat_pressure faults are wired to the default "
                         "dp/sp/tp path only (the pp/moe steppers do "
                         "not thread the telemetry / pressure tables)")
    if (args.overlap_reduce or args.bucket_elems is not None) \
            and (args.pp > 1 or args.moe):
        raise SystemExit("--overlap-reduce/--bucket-elems are wired to "
                         "the default dp/sp/tp path only (the pp/moe "
                         "steppers have their own schedules)")
    # ISSUE 12: --overlap-reduce composes with --emulate_node > 1 now
    # (the unrolled micro chain feeds the last micro-batch's taps) —
    # the old fail-fast is gone
    if args.block_scale and args.mode != "ring":
        raise SystemExit("--block-scale needs --mode ring: the per-block "
                         "scale sidecar rides the ring's packed wire")
    if args.block_scale and (args.pp > 1 or args.moe):
        raise SystemExit("--block-scale is wired to the default dp/sp/tp "
                         "path only (the pp/moe steppers do not thread "
                         "the blocked wire)")
    if args.block_scale and args.grad_man < 2:
        raise SystemExit(f"--block-scale needs a packable gradient format "
                         f"(man_bits >= 2 for the codec's special codes), "
                         f"got e{args.grad_exp}m{args.grad_man}")
    if res["active"]:
        # the guard's verdict must be agreed over EVERY mesh axis the
        # update runs under — tp/pp/ep-sharded leaves legitimately hold
        # different gradients per shard, so a dp-only psum would let
        # model shards take different skip branches (guard.py docstring)
        tx = res["wrap_tx"](tx, axis_name=tuple(mesh.axis_names))
    injector, watchdog = res["injector"], res["watchdog"]
    sentinel, meter = res["sentinel"], res["meter"]
    supervisor, step_table, resync_fn = res["supervisor"], None, None
    psup = res["precision"]
    # observability spine (docs/OBSERVABILITY.md): tracer spans on the
    # step clock, the metrics registry, and the crash flight recorder —
    # all pure host-side observation, so step outputs are bitwise
    # identical with or without --obs-dir (the obs-smoke gate pins it)
    from cpd_tpu.obs import NULL_TRACER
    from cpd_tpu.utils.config import build_obs
    obs = build_obs(args, run="lm",
                    meta={"max_iter": args.max_iter, "mode": args.mode,
                          "grad_format": [args.grad_exp,
                                          args.grad_man]})
    otr = obs["tracer"] if obs["tracer"] is not None else NULL_TRACER
    oreg, oflight = obs["registry"], obs["flight"]
    if watchdog is not None and oflight is not None:
        # dump the ring at FIRE time, on the timer thread — even a
        # wedge that ends in the hard-exit path leaves it on disk
        watchdog.on_trip = lambda ctx: oflight.dump("watchdog")

    def run_meta():
        # ladder state rides every checkpoint's metadata sidecar so a
        # restart/rollback resumes AT the escalated format; the elastic
        # fleet view rides along so a process restart resumes with the
        # same alive set (ISSUE 19)
        meta = {}
        if psup is not None:
            meta["precision"] = psup.state_dict()
        if esup is not None:
            meta["elastic"] = esup.state_dict()
        return meta or None

    ds = SyntheticText(n=4096, seq_len=args.seq_len,
                       vocab_size=args.vocab_size)
    sample = jnp.zeros((1, args.seq_len), jnp.int32)
    from cpd_tpu.utils.config import block_key, overlap_key
    ov_key = overlap_key(args)
    bk_key = block_key(args)
    quant_kw = dict(use_aps=args.use_APS, grad_exp=args.grad_exp,
                    grad_man=args.grad_man, use_kahan=args.use_kahan,
                    mode=args.mode, grad_rounding=args.grad_rounding,
                    grad_seed=args.grad_seed)
    if not (args.pp > 1 or args.moe):
        # the overlapped transport (and the block-scaled ring wire)
        # ride the default dp/sp/tp step only
        quant_kw.update(overlap_reduce=args.overlap_reduce,
                        bucket_elems=args.bucket_elems,
                        block_scale=args.block_scale,
                        block_size=args.block_size)

    if args.pp > 1:
        # GPipe pipeline path (parallel/pipeline.py, train/pp.py)
        from cpd_tpu.models import pipelined_lm
        from cpd_tpu.train import make_pp_eval_step, make_pp_train_step
        from cpd_tpu.train.pp import pp_state_specs
        from cpd_tpu.train.state import TrainState
        pp_model = pipelined_lm(**model_kw, pp_axis="pp", pp_size=args.pp,
                                tp_axis="tp" if args.tp > 1 else None,
                                tp_size=args.tp, vocab_pp=args.vocab_pp)
        variables = pipelined_lm(**model_kw).init(jax.random.PRNGKey(0),
                                                  sample)
        state = TrainState(step=jnp.zeros([], jnp.int32),
                           params=variables["params"], batch_stats={},
                           opt_state=tx.init(variables["params"]))
        step = make_pp_train_step(pp_model, tx, mesh,
                                  n_microbatches=args.n_microbatches,
                                  **quant_kw)
        eval_step = make_pp_eval_step(pp_model, mesh,
                                      n_microbatches=args.n_microbatches)
        specs_fn = (lambda st: pp_state_specs(st, vocab_pp=True)
                    ) if args.vocab_pp else pp_state_specs
        global_batch = args.batch_size * dp
    elif args.moe:
        # expert-parallel path (models/moe.py, train/moe.py)
        from cpd_tpu.models import moe_lm
        from cpd_tpu.train import make_moe_eval_step, make_moe_train_step
        from cpd_tpu.train.moe import moe_state_specs
        from cpd_tpu.train.state import TrainState
        ep = mesh.shape["ep"]
        moe_kw = dict(**model_kw, n_experts=args.n_experts)
        moe_model = moe_lm(**moe_kw, ep_axis="ep" if ep > 1 else None,
                           ep_size=ep)
        variables = moe_lm(**moe_kw).init(jax.random.PRNGKey(0), sample)
        state = TrainState(step=jnp.zeros([], jnp.int32),
                           params=variables["params"], batch_stats={},
                           opt_state=tx.init(variables["params"]))
        step = make_moe_train_step(moe_model, tx, mesh, **quant_kw)
        eval_step = make_moe_eval_step(moe_model, mesh)
        specs_fn = moe_state_specs
        global_batch = args.batch_size * dp * ep
    else:
        from cpd_tpu.train.lm import lm_state_specs
        model = transformer_lm(tp_axis="tp" if args.tp > 1 else None,
                               sp_axis="sp" if args.sp > 1 else None,
                               tp_size=args.tp, sp_mode=args.sp_mode,
                               remat=args.remat,
                               scan_layers=args.scan_layers,
                               n_kv_heads=args.n_kv_heads,
                               dropout_rate=args.dropout, **model_kw)
        # init model: global shapes, but the SAME param-tree layout
        init_model = transformer_lm(scan_layers=args.scan_layers,
                                    n_kv_heads=args.n_kv_heads,
                                    dropout_rate=args.dropout, **model_kw)
        state = create_train_state(init_model, tx, sample,
                                   jax.random.PRNGKey(0))
        tele_kw = dict(quant_stats=res["quant_stats"],
                       sat_fault_plan=res["sat_plan"])
        if supervisor is not None or psup is not None:
            # one or both ladders (docs/RESILIENCE.md): lazily compiled
            # steps keyed by `ladder_step_key` — transport level, eXmY
            # format, or the (level, format) pair; donate=False so a
            # failed verify can discard
            from cpd_tpu.resilience import (StepTable,
                                            level_reduce_kwargs)
            from cpd_tpu.resilience.precision import resolve_ladder_key
            if supervisor is not None:
                from cpd_tpu.parallel.integrity import make_consensus_fns
                _, resync_fn = make_consensus_fns(mesh, "dp")
            lvl_kw = {k: v for k, v in quant_kw.items()
                      if k not in ("mode", "grad_exp", "grad_man",
                                   "block_scale", "block_size")}

            def build_step(key):
                level, fmt = resolve_ladder_key(
                    key, transport_on=supervisor is not None,
                    precision_on=psup is not None, level=args.mode,
                    fmt=(args.grad_exp, args.grad_man),
                    overlap_on=ov_key is not None,
                    block_on=bk_key is not None)
                if supervisor is not None:
                    rkw = level_reduce_kwargs(level, *fmt)
                else:
                    rkw = dict(mode=level, grad_exp=fmt[0],
                               grad_man=fmt[1])
                # block scaling only exists on the ring rung at a
                # packable format (see the resnet18 CLI's gating)
                blk = (args.block_scale and rkw.get("mode") == "ring"
                       and fmt[1] >= 2 and fmt != (8, 23))
                return make_lm_train_step(
                    model, tx, mesh, emulate_node=args.emulate_node,
                    label_smoothing=args.label_smoothing, donate=False,
                    verify_reduce=res["verify"],
                    wire_fault_plan=(res["wire_plan"]
                                     if level == "ring" else None),
                    block_scale=blk, block_size=args.block_size,
                    **rkw, **lvl_kw, **tele_kw)

            step_table = StepTable(build_step)
            step = step_table[ladder_step_key(supervisor, psup,
                                              overlap=ov_key,
                                              block=bk_key)]
        else:
            # no ladder (verify off, or a non-ladder mode like fast):
            # verification, when on, is detection-only agreement checking
            def build_plain_step(m):
                # mesh-parametrized so the elastic path can rebuild the
                # SAME step at a shrunken/regrown world (ISSUE 19)
                return make_lm_train_step(
                    model, tx, m, emulate_node=args.emulate_node,
                    label_smoothing=args.label_smoothing,
                    verify_reduce=res["verify"],
                    wire_fault_plan=res["wire_plan"],
                    **quant_kw, **tele_kw)
            step = build_plain_step(mesh)
        eval_step = make_lm_eval_step(model, mesh)
        specs_fn = lm_state_specs
        global_batch = args.batch_size * dp * args.emulate_node

    # checkpoints of the SHARDED state: orbax saves the global arrays; on
    # restore the state is re-laid-out with the path's PartitionSpecs
    from jax.sharding import NamedSharding, PartitionSpec
    from cpd_tpu.train import CheckpointManager
    manager = CheckpointManager(os.path.abspath(
        os.path.join(args.save_path, "ckpt")), track_best=False,
        integrity=getattr(args, "ckpt_integrity", True))
    start_iter = 0
    restored = manager.restore(state)
    if restored is not None:
        state = restored
        start_iter = int(restored.step)
        if rank == 0:
            print(f"=> resumed from iter {start_iter}")
        if psup is not None:
            # a restart mid-escalation resumes AT the escalated format
            # (the acceptance contract) — the ladder state was saved in
            # the checkpoint's metadata sidecar
            meta = manager.metadata()
            if meta and meta.get("precision"):
                psup.load_state_dict(meta["precision"])
                step = step_table[ladder_step_key(supervisor, psup, overlap=ov_key, block=bk_key)]
                if rank == 0:
                    print(f"=> resumed precision ladder at {psup.name}"
                          + (" (escalated)" if psup.escalated else ""))
    def relayout(st):
        # orbax restores arrays committed to a single device; the step's
        # shard_map needs the path's PartitionSpec layout (also re-run
        # after every rollback restore)
        return jax.device_put(
            st, jax.tree.map(lambda s: NamedSharding(mesh, s),
                             specs_fn(st),
                             is_leaf=lambda s: isinstance(s, PartitionSpec)))

    state = relayout(state)
    # held-out tail of the synthetic corpus for validation (sized to the
    # eval step's data sharding: dp, dp x ep, ... depending on path)
    val_bs = global_batch // args.emulate_node
    val_idx = np.arange(len(ds) - val_bs, len(ds))
    val_toks, val_tgts = ds.batch(val_idx, seed=-1)

    def validate(it):
        m = eval_step(state, jnp.asarray(val_toks), jnp.asarray(val_tgts))
        if rank == 0:
            print(f"Val [{it}]: loss {float(m['loss']):.4f} "
                  f"acc {100 * float(m['accuracy']):.2f}", flush=True)
        writer.add_scalar("val/loss", float(m["loss"]), it)
        return m

    writer = ScalarWriter(os.path.join(args.save_path, "logs"), rank=rank,
                          tensorboard=args.tensorboard)
    progress = ProgressPrinter(args.max_iter, args.print_freq, rank=rank)
    rng = np.random.RandomState(0)
    last = {}
    t0 = now()
    # training indices exclude the held-out validation tail
    train_n = len(ds) - len(val_idx)
    profiler = StepProfiler(args.profile_dir, start=3)
    # SIGTERM → save at the next step boundary and exit cleanly; resume
    # continues at the saved iteration (same scheme as the other trainers)
    from cpd_tpu.train import PreemptionGuard, loss_diverged, preempt_save
    from cpd_tpu.resilience.inject import InjectedPreemption
    guard = PreemptionGuard()
    preempted = diverged = False
    step_no = start_iter
    rollbacks = reseed = 0
    prev_batch = None
    # --- elastic training setup (ISSUE 19, docs/RESILIENCE.md) --------
    elastic_table, elastic_links, last_dt = None, {}, None
    if esup is not None:
        if res["plan"] is not None and res["plan"].elastic_faults():
            # drill mode: heartbeat rows derive from the plan — a pure
            # function of it, no wall clock — so a drill replays its
            # shrink/regrow event sequence exactly
            from cpd_tpu.resilience.elastic import heartbeat_table
            elastic_table = heartbeat_table(res["plan"],
                                            esup.home_world,
                                            args.max_iter)
            elastic_links = {f.step: (int(f.arg) if f.arg >= 0 else 0,
                                      int(f.arg2) if f.arg2 >= 0 else 1)
                             for f in res["plan"].elastic_faults()
                             if f.kind == "link_flaky"}

        def rebuild_elastic(w):
            # re-layout the data axis at runtime: a new mesh over the
            # first w alive hosts' devices rebuilds the compiled step
            # and with it every per-mesh closure (ring/hierarchical
            # transports, reduce caches) at the new world
            nonlocal mesh, step, eval_step, global_batch
            devs = [jax.devices()[h] for h in esup.active_hosts()]
            mesh = make_mesh(dp=w, devices=devs)
            step = build_plain_step(mesh)
            eval_step = make_lm_eval_step(model, mesh)
            global_batch = args.batch_size * w * args.emulate_node

    def batch_for(i):
        # default path: the run-sequential RNG stream (unchanged
        # behavior — watchdog/guard-only runs keep the baseline's exact
        # batch order); rollback path: per-(retry, iter) seeding so a
        # replay draws a DIFFERENT batch order (the re-seeded recovery
        # of docs/RESILIENCE.md), identically on every host
        with otr.span("data", step=i):
            if sentinel is not None:
                r = np.random.RandomState((reseed * 1000003 + i)
                                          % (2 ** 31))
                idx = r.randint(0, train_n, size=global_batch)
            else:
                idx = rng.randint(0, train_n, size=global_batch)
            return ds.batch(idx, seed=i)

    def watchdog_stop():
        watchdog.disarm()     # acknowledge the trip: cancels hard-exit
        meter.bump("watchdog_trips")
        preempt_save(manager, step_no, state, rank,
                     metadata=run_meta(), what="watchdog stop at")

    try:
        it = start_iter + 1
        while it <= args.max_iter:
            if watchdog is not None and watchdog.tripped:
                # the trip's interrupt was absorbed by the SIGINT-trapping
                # PreemptionGuard; honor it at the step boundary
                watchdog_stop()
                preempted = True
                break
            if guard.should_stop():      # collective when multi-host
                if oflight is not None:
                    oflight.dump("preempt")
                preempt_save(manager, step_no, state, rank,
                             metadata=run_meta())
                preempted = True
                break
            profiler.step(it)
            # host faults key on the 0-based optimizer-UPDATE index, the
            # same clock with_fault_injection's grad schedule runs on, so
            # one plan stays in sync across its two executors (and across
            # run_guarded, whose `it` is that index already).  Checkpoint
            # faults are the exception: they key on the saved step's name.
            upd = it - 1
            # --- elastic supervision (ISSUE 19): one heartbeat row per
            # update, BEFORE the step — the evidence is the previous
            # step's per-host timing (plan-derived in drills, the
            # measured step time stood in for every dp host otherwise)
            if esup is not None:
                if elastic_table is not None:
                    row = (elastic_table[upd] if upd < len(elastic_table)
                           else [1.0] * esup.home_world)
                elif last_dt is not None:
                    row = [last_dt] * esup.home_world
                else:
                    row = None
                decision = (esup.on_heartbeats(upd, row)
                            if row is not None else None)
                meter.counts["elastic_hot_steps"] = \
                    esup.counters["hot_steps"]
                meter.counts["elastic_heartbeat_misses"] = \
                    esup.counters["heartbeat_misses"]
                if decision is None and upd in elastic_links:
                    # the in-step collective retry ladder for a flaky
                    # wire into one host (popped: one-shot per spec)
                    host, attempts = elastic_links.pop(upd)
                    for _ in range(attempts):
                        act = esup.on_link_failure(upd, host)
                        if act == "shrink":
                            decision = ("shrink", (host,))
                            meter.bump("elastic_link_escalations")
                            break
                        meter.bump("elastic_link_retries")
                    else:
                        esup.on_step_ok(upd)
                        if rank == 0 and attempts:
                            print(f"=> elastic: flaky link into host "
                                  f"{host} at iter {it} absorbed by "
                                  f"{attempts} in-step retr"
                                  f"{'y' if attempts == 1 else 'ies'}",
                                  file=sys.stderr)
                if decision is not None:
                    what, hosts_ch = decision
                    if what == "shrink":
                        for _ in hosts_ch:
                            meter.bump("elastic_drains")
                        meter.bump("elastic_shrinks")
                        new_w = esup.world
                        rolled = (manager.restore_latest_valid(
                                      state, rank=rank, world=new_w)
                                  if new_w >= 1 else None)
                        if rolled is None:
                            if rank == 0:
                                print(f"=> elastic: host(s) "
                                      f"{list(hosts_ch)} lost at iter "
                                      f"{it} and no world to shrink "
                                      f"onto — stopping", file=sys.stderr)
                            if oflight is not None:
                                oflight.dump("elastic")
                            preempted = True
                            break
                        rebuild_elastic(new_w)
                        state = relayout(rolled.state)
                        meter.bump("restores")
                        step_no = int(rolled.step)
                        it = step_no + 1
                        if rank == 0:
                            print(f"=> elastic: drained host(s) "
                                  f"{list(hosts_ch)}, world -> {new_w} "
                                  f"(hosts {list(esup.active_hosts())})"
                                  f", resumed from iter {step_no}",
                                  file=sys.stderr)
                        if oflight is not None:
                            oflight.record("elastic_shrink",
                                           step=step_no)
                        continue
                    # regrow: the live state is healthy — seal it, then
                    # rebuild UP onto the returning host (zero steps
                    # lost by construction)
                    meter.bump("elastic_regrows")
                    manager.save(step_no, state, force=True,
                                 metadata=run_meta())
                    manager.wait()
                    rebuild_elastic(esup.world)
                    state = relayout(state)
                    if rank == 0:
                        print(f"=> elastic: host(s) {list(hosts_ch)} "
                              f"rejoined after probation, world -> "
                              f"{esup.world}", file=sys.stderr)
            try:
                if injector is not None:
                    injector.maybe_preempt(upd)
                    action = injector.batch_action(upd)
                else:
                    action = None
                if action == "dup" and prev_batch is not None:
                    meter.bump("batches_duplicated")
                    toks, tgts = prev_batch
                elif action == "drop":
                    meter.bump("batches_dropped")
                    toks, tgts = batch_for(it + args.max_iter)
                else:
                    toks, tgts = batch_for(it)
                if injector is not None:
                    # batch_scale touches float leaves only (a no-op on
                    # int token batches); batch_nan raises loudly there —
                    # LM gradient faults belong to the grad_* kinds
                    toks, tgts = injector.corrupt_batch(upd, (toks, tgts))
                prev_batch = (toks, tgts)
                if watchdog is not None:
                    watchdog.arm(it, loss=last.get("loss"))
                if injector is not None:
                    injector.maybe_stall(upd)
                prev_state = state    # verified-reduce discard target
                t_step = now()
                with otr.span("step", step=it):
                    # the whole jitted fwd+bwd+reduce+optimizer program
                    # plus the metric device-sync; per-bucket reduce
                    # detail rides the reduce_* metrics (registry)
                    state, m = step(state, jnp.asarray(toks),
                                    jnp.asarray(tgts))
                    last = {k: float(v) for k, v in m.items()}  # sync
                last_dt = now() - t_step
                if esup is not None:
                    esup.on_step_ok(upd)
                if watchdog is not None:
                    watchdog.disarm()
            except KeyboardInterrupt:
                if watchdog is not None and watchdog.tripped:
                    watchdog_stop()
                    preempted = True
                    break
                raise
            except InjectedPreemption:
                if oflight is not None:
                    oflight.dump("preempt")
                preempt_save(manager, step_no, state, rank,
                             metadata=run_meta(),
                             what="injected preemption at")
                meter.bump("preemptions")
                preempted = True
                break
            # --- verified-reduce supervision (ISSUE 4) ----------------
            # reduce_ok == 0: the reduce failed its checksums/agreement.
            # Discard the corrupted update (donate=False keeps the
            # pre-step state alive) and walk the transport ladder; the
            # `continue` leaves `it` unchanged, so the retry replays the
            # SAME update index — a deterministic injected wire fault
            # re-fires and forces the downgrade, exactly as in
            # run_guarded.
            if supervisor is None and res["verify"] and float(
                    last.get("reduce_ok", 1.0)) == 0.0:
                # non-ladder mode (fast): detection only — count + warn
                meter.bump("wire_faults_detected")
                if rank == 0:
                    print(f"=> reduce verify FAILED at iter {it} (mode "
                          f"{args.mode} has no transport ladder: "
                          f"detection only)", file=sys.stderr)
            if supervisor is not None and float(
                    last.get("reduce_ok", 1.0)) == 0.0:
                meter.bump("wire_faults_detected")
                state = prev_state
                action = supervisor.on_failure(upd)
                if action == "give_up":
                    if rank == 0:
                        print(f"=> verified reduce failed at the fp32 "
                              f"transport floor (iter {it}) — not a "
                              f"wire problem; stopping", file=sys.stderr)
                    diverged = True
                    break
                if action == "downgrade":
                    meter.bump("transport_downgrades")
                    state = resync_fn(state)
                    meter.bump("resyncs")
                    step = step_table[ladder_step_key(supervisor, psup, overlap=ov_key, block=bk_key)]
                    if rank == 0:
                        print(f"=> wire fault detected at iter {it} "
                              f"(hop_bad "
                              f"{int(last.get('reduce_hop_bad', 0))}, "
                              f"gather_bad "
                              f"{int(last.get('reduce_gather_bad', 0))})"
                              f" — transport downgraded to "
                              f"{supervisor.mode}, replicas re-synced "
                              f"from rank 0", file=sys.stderr)
                else:
                    meter.bump("reduce_retries")
                    if rank == 0:
                        print(f"=> wire fault detected at iter {it} — "
                              f"update discarded, retrying on the "
                              f"{supervisor.mode} transport",
                              file=sys.stderr)
                continue
            if supervisor is not None and \
                    supervisor.on_success(upd) == "upgrade":
                meter.bump("transport_upgrades")
                step = step_table[ladder_step_key(supervisor, psup, overlap=ov_key, block=bk_key)]
                if rank == 0:
                    print(f"=> transport probation passed at iter {it}: "
                          f"back to {supervisor.mode}", file=sys.stderr)
            step_no = it
            if meter is not None:
                meter.observe_metrics(last)
            if oreg is not None:
                oreg.absorb_step_metrics(last, it)
            if oflight is not None:
                oflight.record("step", step=it, loss=last["loss"])
            # --- precision-ladder supervision (ISSUE 5) ---------------
            # host decision on the psum-agreed prec_wire_* telemetry;
            # escalation re-formats the NEXT step (this update was
            # already guarded in-step if its values went non-finite)
            if psup is not None:
                pact = psup.on_metrics(upd, last)
                if psup.last_hot:
                    meter.bump("sat_hot_steps")
                if pact is not None:
                    meter.bump("precision_escalations"
                               if pact == "escalate"
                               else "precision_deescalations")
                    step = step_table[ladder_step_key(supervisor, psup, overlap=ov_key, block=bk_key)]
                    if rank == 0:
                        how = ("escalated" if pact == "escalate"
                               else "probation passed: back")
                        print(f"=> precision ladder {how} to "
                              f"{psup.name} at iter {it} (sat "
                              f"{int(last.get('prec_wire_sat', 0))}/"
                              f"{int(last.get('prec_wire_total', 0))} "
                              f"nan "
                              f"{int(last.get('prec_wire_nan', 0))})",
                              file=sys.stderr)
            if injector is not None:
                last["loss"] = injector.fault_loss(upd, last["loss"])
            # a guard-skipped step's loss metric may be poisoned by the
            # bad batch/grads; the anomaly was already handled in-step
            guard_ok = float(last.get("guard_ok", 1.0)) != 0.0
            if sentinel is not None:
                if guard_ok and sentinel.update(last["loss"]):
                    if rank == 0:
                        print(f"=> divergence sentinel tripped at iter "
                              f"{it} (loss {last['loss']:.4g})",
                              file=sys.stderr)
                    rolled = None
                    if rollbacks < args.max_rollbacks:
                        rolled = manager.restore_latest_valid(state,
                                                              rank=rank)
                    if rolled is None:
                        diverged = True
                        break
                    for _bad in rolled.skipped:
                        meter.bump("ckpts_invalid")
                    if psup is not None and (rolled.metadata or {}
                                             ).get("precision"):
                        # replaying at home would re-diverge into the
                        # saturation the escalation escaped
                        psup.load_state_dict(rolled.metadata["precision"])
                        step = step_table[ladder_step_key(supervisor,
                                                          psup,
                                                          overlap=ov_key,
                                                          block=bk_key)]
                    state = relayout(rolled.state)
                    step_no = int(rolled.step)
                    it = step_no + 1
                    rollbacks += 1
                    reseed = rollbacks
                    meter.bump("rollbacks")
                    meter.bump("restores")
                    if oflight is not None:
                        oflight.record("rollback", step=step_no)
                        oflight.dump("rollback")
                    sentinel.reset()
                    if rank == 0:
                        print(f"=> rolled back to iter {step_no} "
                              f"(retry {rollbacks}/{args.max_rollbacks}, "
                              f"re-seeded data order)", file=sys.stderr)
                    if args.rollback_backoff > 0:
                        time.sleep(args.rollback_backoff
                                   * (2 ** (rollbacks - 1)))
                    continue
            elif guard_ok and loss_diverged(last["loss"], f"iter {it}",
                                            rank):
                diverged = True
                break
            progress.maybe_print(it, _suffix=meter.suffix(),
                                 Loss=last["loss"],
                                 Acc=100 * last["accuracy"],
                                 TokPerSec=global_batch * args.seq_len * it
                                 / max(now() - t0, 1e-9))
            writer.add_scalar("train/loss", last["loss"], it)
            if it % args.val_freq == 0 or it == args.max_iter:
                with otr.span("validate", step=it):
                    validate(it)
            if it % args.ckpt_freq == 0 or it == args.max_iter:
                # force under resilience: a rollback replay must be able
                # to overwrite the stale/corrupt copy of this step
                with otr.span("checkpoint", step=it):
                    manager.save(it, state, force=res["active"],
                                 metadata=run_meta())
                if injector is not None:
                    # the fault must land on the FINAL bytes — without
                    # integrity the save is still async at this point
                    manager.wait()
                if injector is not None and injector.corrupt_checkpoint(
                        it, manager.directory):
                    if rank == 0:
                        print(f"=> injected checkpoint corruption at "
                              f"step {it}", file=sys.stderr)
            it += 1
    finally:
        guard.uninstall()
        if watchdog is not None:
            watchdog.close()
        # close() stops an in-flight jax.profiler trace even when the
        # loop died inside the window (watchdog interrupt, injected
        # fault) — leaking a running trace poisons every later
        # start_trace in this process (ISSUE 11 satellite)
        profiler.close()
    from cpd_tpu.resilience import report_unfired
    if esup is not None and res["plan"] is not None:
        # the elastic harness owns its kinds' accounting (mirrors
        # run_elastic): anything scheduled past the last processed
        # update, or aimed at a host outside the fleet, never manifested
        leftover = sorted(
            f for f in res["plan"].elastic_faults()
            if f.step >= step_no or int(max(f.arg, 0)) >= esup.home_world)
        if leftover:
            meter.bump("faults_unfired", len(leftover))
            if rank == 0:
                print(f"=> elastic plan: {len(leftover)} spec(s) never "
                      f"fired: {leftover}", file=sys.stderr)
    # wire faults only fire when the default path baked a ring-mode
    # table in — a wire_* spec on any other run must read as UNFIRED
    report_unfired(injector, n_steps=args.max_iter, meter=meter, rank=rank,
                   wire_armed=(not (args.pp > 1 or args.moe)
                               and (supervisor.home == "ring"
                                    if supervisor is not None
                                    else args.mode == "ring")),
                   # sat tables only ride the default-path steppers (a
                   # pp/moe run with sat specs exits up front, but keep
                   # the accounting honest regardless)
                   sat_armed=not (args.pp > 1 or args.moe),
                   host_armed=esup is not None)
    jax.block_until_ready(state.params)
    manager.wait()
    manager.close()
    dt = now() - t0
    ran = step_no - start_iter
    if rank == 0 and not (preempted or diverged):
        if last:
            # count only the iters THIS run executed — a partial resume
            # must not overstate the throughput
            print(f"done: {ran} iters in {dt:.1f}s "
                  f"({ran * global_batch * args.seq_len / dt:.0f}"
                  f" tok/s) final loss {last['loss']:.4f}")
        else:
            # resumed at/past max_iter: no step ran — say so instead of
            # printing a placeholder nan that reads like divergence
            print(f"done: resumed at iter {start_iter}, nothing left to "
                  f"train (max_iter {args.max_iter})")
    sampled = None
    if args.sample > 0 and not (preempted or diverged):
        from jax.sharding import NamedSharding, PartitionSpec
        from cpd_tpu.models import generate
        toks, _ = ds.batch(np.arange(1), seed=0)
        prompt = jnp.asarray(toks[:, :min(8, args.seq_len)], jnp.int32)
        # params were laid out per lm_state_specs (tp-sharded leaves when
        # tp>1); re-lay them out fully replicated — a compiled all-gather
        # that is multi-host safe, unlike device_get on a sharded Array —
        # then decode single-device
        gather = jax.jit(lambda p: p,
                         out_shardings=NamedSharding(mesh, PartitionSpec()))
        out = generate(init_model, jax.device_get(gather(state.params)),
                       prompt, max_new_tokens=args.sample,
                       temperature=args.sample_temperature,
                       top_k=args.sample_top_k, top_p=args.sample_top_p,
                       rng=(jax.random.PRNGKey(args.sample_seed)
                            if args.sample_temperature > 0 else None))
        sampled = np.asarray(out)[0].tolist()
        if rank == 0:
            how = ("greedy" if args.sample_temperature == 0 else
                   f"T={args.sample_temperature} k={args.sample_top_k} "
                   f"p={args.sample_top_p}")
            print(f"sample ({how}, {args.sample} new tokens): {sampled}")
    if args.export_torch and not (preempted or diverged):
        from jax.sharding import NamedSharding, PartitionSpec
        from cpd_tpu.interop import (export_transformer_lm,
                                     save_torch_checkpoint)
        # same multi-host-safe re-layout as the sample path above:
        # compiled all-gather to replicated, then host copies; only rank
        # 0 writes (every host holds the same gathered values)
        gather = jax.jit(lambda p: p,
                         out_shardings=NamedSharding(mesh, PartitionSpec()))
        params_host = jax.device_get(gather(state.params))
        if rank == 0:
            sd = export_transformer_lm({"params": params_host})
            save_torch_checkpoint(sd, args.export_torch,
                                  wrapper="state_dict")
            print(f"=> exported torch state_dict {args.export_torch}")
    writer.close()
    from cpd_tpu.utils.config import finish_obs
    obs_out = finish_obs(obs, meter=meter, last=last, step_no=step_no,
                         supervisor=supervisor, precision=psup,
                         elastic=esup, rank=rank, preempted=preempted,
                         diverged=diverged)
    return {"step": step_no, "diverged": diverged,
            **({"resilience": meter.as_dict()} if res["active"] else {}),
            **({"obs": obs_out} if obs_out is not None else {}),
            **({"sample": sampled} if sampled is not None else {}), **last}


if __name__ == "__main__":
    res = main()
    sys.exit(3 if res.get("diverged") else 0)
