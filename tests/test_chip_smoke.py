"""chip_smoke.py off the chip: it must refuse to run, and its three phase
functions must pass their own checks at toy size on the CPU mesh — so a
chip-minute is never spent finding a bug the CPU could have shown."""

import os
import subprocess
import sys
import types

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def smoke():
    import chip_smoke
    return chip_smoke


def test_refuses_cpu_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=_REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "needs a TPU" in proc.stderr
    assert proc.stdout == ""             # no result, nothing computed


@pytest.mark.slow   # ~30 s: four CLI epochs and two checkpoint dirs
def test_train_phases_at_toy_size(smoke):
    toy = dict(arch="tiny", image_size=32, batch_size=1, num_classes=10,
               steps_per_epoch=2)
    with smoke.CompileMeter() as meter:
        faithful = smoke.train_phase(meter, "faithful", **toy)
        ring = smoke.train_phase(meter, "ring",
                                 reference_loss=faithful["train_loss"],
                                 **toy)
    assert faithful["steps"] == faithful["checkpoint_step"] == 4
    assert faithful["global_batch"] == 8     # conftest's 8 virtual devices
    assert faithful["compile_s"] > 0
    assert ring["rel_to_reference"] <= 1e-2


def test_serve_phase_at_toy_size(smoke):
    with smoke.CompileMeter() as meter:
        facts = smoke.serve_phase(
            meter, lm_kw=dict(vocab_size=128, d_model=64, n_layers=2,
                              n_heads=4, d_ff=128),
            prompt_lens=(9, 17), max_new=4, max_seq=32)
    assert facts["finished"] == 2 and facts["tokens_generated"] == 8
    assert facts["tokens_match"]


def test_a_failing_phase_fails_the_run(smoke, monkeypatch):
    """Nothing is caught and noted: a phase that raises ends main() with
    the exception, i.e. a nonzero exit."""
    import jax

    import cpd_tpu.ops

    fake = types.SimpleNamespace(platform="tpu", device_kind="fake v5e")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    monkeypatch.setattr(cpd_tpu.ops, "interpret_mode", lambda: False)

    def boom(*a, **k):
        raise ValueError("phase blew up")

    monkeypatch.setattr(smoke, "train_phase", boom)
    with pytest.raises(ValueError, match="phase blew up"):
        smoke.main()


def test_a_failed_check_raises(smoke):
    with pytest.raises(RuntimeError, match="loss not finite"):
        smoke.require(False, "loss not finite")
