"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py          # on a machine with a TPU; one process

Drives the repo's two hot paths once, through the entry points a user
calls, at the full width of the models the repo benchmarks:

  train-faithful  examples/resnet50/main.py `main([...])`: ResNet-50,
                  224x224, batch 32/chip, e5m2 APS, --mode faithful,
                  synthetic data, 2 epochs x 8 steps (the second epoch is
                  the steady one: the first holds every compile).  Also
                  the eval step, the prefetcher and two orbax saves.
  train-ring      the same command line with --mode ring: on one chip the
                  first-hop pack kernel (ops/quantize.py), on four the
                  whole packed eXmY ring.  Loss within 1e-2 relative of
                  train-faithful's (different summation orders).
  serve           a ServeEngine over the widest LM the repo benchmarks
                  (vocab 32000, d512, 8 layers, 8 heads, d_ff 2048),
                  kv_format (5, 2), XLA attention: four requests, prompts
                  of 64-128 tokens, 32 new tokens each; a second identical
                  engine must produce the same tokens.

It refuses to run unless `jax.devices()[0].platform == "tpu"`
(`ops.require_tpu`: exit 2, nothing computed), holds every visible chip in this one process, and lets
any failed check or exception end the run with a nonzero exit.  The last
line of stdout is one JSON object naming the device as jax reports it.

Each phase prints its compile seconds (jax's own trace + lower + backend-
compile durations, with the persistent-cache hit count), its steady
seconds per step or per token, and the check it passed.  Those are facts
about this run on this device, not benchmark metrics.
"""

from __future__ import annotations

import importlib.metadata
import json
import math
import os
import sys
import tempfile

_REPO = os.path.dirname(os.path.abspath(__file__))
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

# the serving benchmark's LM (bench.py's LM arm) and this smoke's traffic
SERVE_LM = dict(vocab_size=32000, d_model=512, n_layers=8, n_heads=8,
                d_ff=2048)
SERVE_PROMPT_LENS = (64, 96, 128, 80)


class CompileMeter:
    """Sums jax's compile-duration events and counts persistent-cache
    requests/hits while installed; `take()` reads and resets."""

    def __init__(self):
        self.secs = 0.0
        self.requests = 0
        self.hits = 0

    def _on_duration(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            self.secs += secs

    def _on_event(self, event, **_):
        if event == _CACHE_REQUEST:
            self.requests += 1
        elif event == _CACHE_HIT:
            self.hits += 1

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def take(self) -> dict:
        out = {"compile_s": round(self.secs, 2),
               "cache_requests": self.requests, "cache_hits": self.hits}
        self.secs, self.requests, self.hits = 0.0, 0, 0
        return out


def require(cond: bool, what: str) -> None:
    """A failed check ends the run: nothing is caught and noted."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _report(phase: str, facts: dict) -> dict:
    print(f"phase {phase}: " + json.dumps(facts), flush=True)
    return facts


def train_phase(meter: CompileMeter, mode: str, *, arch: str = "resnet50",
                image_size: int = 224, batch_size: int = 32,
                num_classes: int = 1000, steps_per_epoch: int = 8,
                reference_loss: float | None = None) -> dict:
    """Two epochs of the ResNet-50 trainer CLI in `mode`; returns the
    phase's facts.  `reference_loss`: another mode's train loss for the
    same arch, seed and steps, to agree with within 1e-2 relative."""
    import jax

    examples = os.path.join(_REPO, "examples")
    if examples not in sys.path:
        sys.path.insert(0, examples)
    from resnet50.main import main as train_main

    from cpd_tpu.obs.timing import now
    from cpd_tpu.train import CheckpointManager

    epochs = 2
    with tempfile.TemporaryDirectory(prefix=f"chip_smoke_{mode}_") as run:
        ckpt = os.path.join(run, "ckpt")
        t0 = now()
        res = train_main([
            "--arch", arch, "--image-size", str(image_size),
            "--batch-size", str(batch_size),
            "--val-batch-size", str(batch_size),
            "--num-classes", str(num_classes),
            "--use_APS", "--grad_exp", "5", "--grad_man", "2",
            "--mode", mode, "--epochs", str(epochs),
            "--max-batches-per-epoch", str(steps_per_epoch),
            "--checkpoint-dir", ckpt,
            "--log-dir", os.path.join(run, "logs")])
        wall = now() - t0
        manager = CheckpointManager(ckpt)
        saved_step = manager.latest_step()
        manager.close()

    steps = epochs * steps_per_epoch
    global_batch = batch_size * len(jax.devices())
    require(not res["diverged"], f"{mode}: trainer reported divergence")
    require(res.get("epoch") == epochs - 1,
            f"{mode}: last epoch {res.get('epoch')} != {epochs - 1}")
    for k in ("train_loss", "val_loss"):
        require(math.isfinite(res[k]), f"{mode}: {k}={res[k]} not finite")
    require(saved_step == steps,
            f"{mode}: saved checkpoint step {saved_step} != {steps} steps")
    facts = dict(meter.take(), arch=arch, mode=mode, steps=steps,
                 global_batch=global_batch, wall_s=round(wall, 2),
                 # epoch 1 holds no compile: its rate is the CLI's steady
                 # state, host input pipeline included
                 steady_s_per_step=round(global_batch / res["img_per_sec"],
                                         4),
                 train_loss=res["train_loss"], val_loss=res["val_loss"],
                 checkpoint_step=saved_step)
    if reference_loss is not None:
        rel = abs(res["train_loss"] - reference_loss) / abs(reference_loss)
        require(rel <= 1e-2, f"{mode}: train loss {res['train_loss']} vs "
                             f"reference {reference_loss}: rel {rel:.3e}")
        facts["rel_to_reference"] = rel
    return _report(f"train-{mode}", facts)


def check_device_memory() -> list:
    """Every visible device must have held something."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats()
        require(bool(stats) and stats.get("peak_bytes_in_use", 0) > 0,
                f"device {d} reports no peak memory use: {stats}")
        peaks.append(stats["peak_bytes_in_use"])
    print("peak_bytes_in_use per device: " + json.dumps(peaks), flush=True)
    return peaks


def serve_phase(meter: CompileMeter, *, lm_kw: dict = SERVE_LM,
                prompt_lens: tuple = SERVE_PROMPT_LENS, max_new: int = 32,
                max_seq: int = 256) -> dict:
    """Serve `len(prompt_lens)` requests through two identical engines."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cpd_tpu.models import transformer_lm
    from cpd_tpu.obs.timing import now
    from cpd_tpu.serve import Request, ServeEngine

    model = transformer_lm(**lm_kw)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.RandomState(0)
    prompts = [tuple(int(t) for t in
                     rng.randint(0, lm_kw["vocab_size"], size=n))
               for n in prompt_lens]

    def serve_once():
        eng = ServeEngine(model, params, n_slots=len(prompts),
                          max_seq=max_seq, kv_format=(5, 2))
        for rid, prompt in enumerate(prompts):
            eng.submit(Request(rid=rid, prompt=prompt,
                               max_new_tokens=max_new))
        t0 = now()
        eng.run_until_drained(max_steps=100 * max_seq)
        dt = now() - t0
        corrupt = eng.scrub()
        return eng, dt, corrupt

    first, dt_first, corrupt = serve_once()
    compile_facts = meter.take()
    second, dt_second, corrupt2 = serve_once()

    for eng, bad in ((first, corrupt), (second, corrupt2)):
        require(eng.unresolved() == [],
                f"unresolved requests {eng.unresolved()}")
        for rid in range(len(prompts)):
            require(rid in eng.finished, f"request {rid} not FINISHED")
            require(len(eng.finished[rid]) == max_new,
                    f"request {rid}: {len(eng.finished[rid])} tokens")
        require(bad == [] and eng.counters["kv_inline_detects"] == 0
                and eng.counters["kv_pages_corrupt"] == 0,
                f"digest failures: scrub {bad}, counters {eng.counters}")
    same = all(first.finished[r] == second.finished[r]
               for r in range(len(prompts)))
    require(same, "second identical engine produced different tokens")
    n_tok = second.counters["tokens_generated"]
    return _report("serve", dict(
        compile_facts, requests=len(prompts), prompt_tokens=sum(prompt_lens),
        tokens_generated=n_tok, engine_steps=second.step_index,
        first_engine_wall_s=round(dt_first, 2),
        # the second engine reuses every compiled step
        steady_s_per_token=round(dt_second / n_tok, 5),
        finished=len(prompts), digest_failures=0, tokens_match=True))


def main() -> int:
    import jax
    import jaxlib

    from cpd_tpu import native
    from cpd_tpu.ops import interpret_mode, require_tpu
    from cpd_tpu.utils import default_cache_dir, enable_compile_cache

    devices = require_tpu("chip_smoke")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    enable_compile_cache()
    cache_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    print("device: " + json.dumps(device), flush=True)
    print(f"versions: jax {jax.__version__} jaxlib {jaxlib.__version__} "
          f"libtpu {importlib.metadata.version('libtpu')}", flush=True)
    print(f"compile cache: {cache_env or default_cache_dir()} "
          f"({'env' if cache_env else 'default'})", flush=True)
    print("native: " + ("C++ library" if native.available()
                        else "numpy paths (no C++ compiler)"), flush=True)
    require(not interpret_mode(), "Pallas interpret mode is on")

    with CompileMeter() as meter:
        faithful = train_phase(meter, "faithful")
        train_phase(meter, "ring", reference_loss=faithful["train_loss"])
        check_device_memory()
        serve_phase(meter)

    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
