"""Smoke tests for the example CLI trainers — the end-to-end entry points
mirroring the reference's example/ scripts (SURVEY.md C18-C20, C22), run
with tiny synthetic workloads on the 8-device virtual CPU mesh.

These are the integration layer of the test pyramid the reference lacks
(SURVEY.md §4): each trainer must parse its reference-parity flags, build
the sharded quantized step, run real iterations, checkpoint, and report
metrics through the reference's log line protocol.
"""

import json
import math
import os

import numpy as np
import pytest

# every test here compiles a full trainer graph — the compile-heavy tier
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def tiny_cifar(tmp_path_factory, tiny_cifar_factory):
    return tiny_cifar_factory(tmp_path_factory.mktemp("cifar"))


@pytest.mark.parametrize("mode", ["fast", "faithful"])
def test_resnet18_trainer_aps_smoke(tiny_cifar, tmp_path, capsys, mode):
    from resnet18_cifar.train import main

    save = str(tmp_path / "ckpt")
    prof = str(tmp_path / "trace")
    extra = ["--profile-dir", prof] if mode == "fast" else []
    res = main(["--use_APS", "--grad_exp", "5", "--grad_man", "2",
                "--emulate_node", "2", "--use_lars", "--arch", "tiny",
                "--data-root", tiny_cifar, "--max-iter", "4",
                "--batch_size", "2", "--val_freq", "4",
                "--save_path", save, "--mode", mode] + extra)
    if mode == "fast":
        # jax.profiler must have written trace artifacts for steps 3..4
        found = [os.path.join(r, f) for r, _, fs in os.walk(prof) for f in fs]
        assert found, "no profiler trace artifacts written"
    assert res["step"] == 4
    assert math.isfinite(res["loss"])
    out = capsys.readouterr().out
    assert "* All Loss" in out            # draw_curve's grep contract
    # scalar stream exists and parses
    jsonl = os.path.join(save, "logs", "scalars.jsonl")
    assert os.path.isfile(jsonl)
    with open(jsonl) as f:
        recs = [json.loads(line) for line in f]
    assert any(r["tag"] == "train/loss" for r in recs)
    # checkpoint written at the val_freq boundary -> resumable
    from cpd_tpu.train import CheckpointManager
    mgr = CheckpointManager(save, track_best=False)
    assert mgr.latest_step() == 4
    mgr.close()


def test_resnet18_trainer_overlap_smoke(tiny_cifar, tmp_path):
    """--overlap-reduce end to end (ISSUE 8): the bucketed in-backward
    ring transport trains through the full CLI harness."""
    from resnet18_cifar.train import main

    res = main(["--use_APS", "--grad_exp", "5", "--grad_man", "2",
                "--emulate_node", "1", "--arch", "tiny",
                "--data-root", tiny_cifar, "--max-iter", "3",
                "--batch_size", "2", "--val_freq", "4",
                "--save_path", str(tmp_path / "ckpt"), "--mode", "ring",
                "--overlap-reduce", "--bucket-elems", "4096"])
    assert res["step"] == 3
    assert math.isfinite(res["loss"])


def test_resnet18_halts_on_nonfinite_loss(tiny_cifar, tmp_path, capsys):
    """A diverged run (NaN/inf loss) must stop with a clear verdict — a
    controlled stop (diverged=True in the result, teardown runs), not an
    exception that would kill in-process harnesses like aps_golden."""
    from resnet18_cifar.train import main

    res = main(["--arch", "tiny", "--data-root", tiny_cifar,
                "--max-iter", "8", "--batch_size", "2", "--val_freq", "8",
                "--peak-lr", "1e8",
                "--save_path", str(tmp_path / "ck"), "--mode", "fast"])
    assert res["diverged"] is True
    assert res["step"] < 8                     # stopped early
    err = capsys.readouterr().err
    assert "non-finite loss" in err and "diverged" in err


def test_resnet18_trainer_quant_optimizer_smoke(tiny_cifar, tmp_path):
    """--opt_exp/--opt_man: e5m2 Kahan momentum buffer through the CLI."""
    from resnet18_cifar.train import main

    res = main(["--arch", "tiny", "--data-root", tiny_cifar,
                "--max-iter", "3", "--batch_size", "2", "--val_freq", "3",
                "--opt_exp", "5", "--opt_man", "2", "--opt_kahan",
                "--save_path", str(tmp_path / "ck"), "--mode", "fast"])
    assert res["step"] == 3
    assert math.isfinite(res["loss"])


def test_resnet18_trainer_shampoo_lite_smoke(tiny_cifar, tmp_path):
    """--optimizer shampoo-lite at e5m7 ring statistics (ISSUE 15):
    the second-order updater owns the collective (reduce_in_update,
    like ZeRO) and the smoke must train inside the pinned loss
    envelope — CE for 10 classes starts at ln(10) ~= 2.303; a broken
    preconditioner (wrong grafting scale, bad inverse root) blows
    straight past it in the first steps."""
    from resnet18_cifar.train import main

    res = main(["--optimizer", "shampoo-lite",
                "--shampoo-stat-exp", "5", "--shampoo-stat-man", "7",
                "--arch", "tiny", "--data-root", tiny_cifar,
                "--max-iter", "3", "--batch_size", "2",
                "--val_freq", "3", "--use_kahan",
                "--save_path", str(tmp_path / "ck")])
    assert res["step"] == 3
    assert math.isfinite(res["loss"])
    assert res["loss"] <= 2.6, \
        f"shampoo-lite smoke loss {res['loss']:.3f} outside the " \
        f"pinned envelope (measured ~2.30 on this fixture)"
    assert not res["diverged"]


def test_resnet18_shampoo_lite_flag_conflicts(tiny_cifar, tmp_path):
    from resnet18_cifar.train import main

    base = ["--optimizer", "shampoo-lite", "--arch", "tiny",
            "--data-root", tiny_cifar, "--max-iter", "1",
            "--batch_size", "2", "--val_freq", "1",
            "--save_path", str(tmp_path / "ck")]
    for bad in (["--use_lars"], ["--opt_exp", "5", "--opt_man", "2"],
                ["--zero1"], ["--clip-grad", "1.0"],
                ["--overlap-reduce"], ["--bucket-elems", "4096"]):
        with pytest.raises(SystemExit):
            main(base + bad)
    # review regression: an explicit non-quant optimizer must not
    # silently drop the quantized-momentum flags (auto would have
    # selected quant_sgd for them)
    with pytest.raises(SystemExit, match="ignore"):
        main(["--optimizer", "sgd", "--opt_exp", "5", "--opt_man", "2",
              "--arch", "tiny", "--data-root", tiny_cifar,
              "--max-iter", "1", "--batch_size", "2", "--val_freq", "1",
              "--save_path", str(tmp_path / "ck2")])


def test_resnet18_trainer_evaluate_flag(tiny_cifar):
    from resnet18_cifar.train import main

    res = main(["-e", "--arch", "tiny", "--data-root", tiny_cifar])
    assert set(res) == {"loss", "top1", "top5"}


def test_davidnet_trainer_smoke(tiny_cifar, capsys):
    from davidnet.dawn import main

    # faithful mode: the gather+ordered-scan collective end-to-end
    res = main(["--epoch", "2", "--batch_size", "16", "--arch", "tiny",
                "--max-batches-per-epoch", "2", "--half", "1",
                "--use_APS", "--grad_exp", "5", "--grad_man", "2",
                "--loss_scale", "128", "--data-root", tiny_cifar,
                "--mode", "faithful"])
    assert res["epoch"] == 2
    assert math.isfinite(res["train loss"])
    out = capsys.readouterr().out
    assert "epoch\thours\ttop1Accuracy" in out   # DAWNBench TSV header


def test_resnet50_trainer_smoke_and_resume(tmp_path, capsys):
    from resnet50.main import main

    ckpt = str(tmp_path / "ck")
    logs = str(tmp_path / "logs")
    argv = ["--batch-size", "1", "--epochs", "1", "--arch", "tiny",
            "--num-classes", "10",
            "--max-batches-per-epoch", "2", "--image-size", "32",
            "--use-APS", "--grad_exp", "5", "--grad_man", "2",
            "--emulate-node", "2", "--checkpoint-dir", ckpt,
            "--log-dir", logs, "--mode", "faithful"]
    res = main(argv)
    assert res["epoch"] == 0
    assert math.isfinite(res["train_loss"])
    # second invocation must auto-resume past epoch 0 and do nothing
    res2 = main(argv)
    out = capsys.readouterr().out
    assert "auto-resumed" in out
    assert "epoch" not in res2             # all epochs already done


def _make_fake_guard(trigger_after_polls):
    """Deterministic PreemptionGuard stand-in: should_stop() turns True
    after N polls, so trainer save/resume logic is exercised without real
    signal timing (the signal mechanics have their own unit test)."""

    class FakeGuard:
        def __init__(self, *a, **k):
            self.polls = 0

        @property
        def triggered(self):
            return self.polls > trigger_after_polls

        def should_stop(self):
            self.polls += 1
            return self.triggered

        def uninstall(self):
            pass

    return FakeGuard


def test_preemption_guard_signal_mechanics():
    import signal

    from cpd_tpu.train import PreemptionGuard

    guard = PreemptionGuard()
    try:
        assert not guard.triggered
        os.kill(os.getpid(), signal.SIGTERM)   # delivered synchronously
        assert guard.triggered
    finally:
        guard.uninstall()
    # uninstall restored the previous disposition
    assert signal.getsignal(signal.SIGTERM) != guard._handle


def test_resnet50_preempt_saves_and_resumes_mid_epoch(tmp_path, capsys,
                                                      monkeypatch):
    """SIGTERM mid-epoch → checkpoint with (epoch, iter) → exact resume.

    The guard's signal mechanics are unit-tested above; here a fake guard
    triggers deterministically after one step so the trainer's
    save/resume logic is exercised without real signal timing."""
    from cpd_tpu.train import CheckpointManager, checkpoint
    from resnet50.main import main

    FakeGuard = _make_fake_guard(1)

    ckpt = str(tmp_path / "ck")
    argv = ["--batch-size", "1", "--epochs", "1", "--arch", "tiny",
            "--num-classes", "10", "--max-batches-per-epoch", "3",
            "--image-size", "32", "--use-APS", "--grad_exp", "5",
            "--grad_man", "2", "--checkpoint-dir", ckpt,
            "--log-dir", str(tmp_path / "logs"), "--mode", "fast"]

    monkeypatch.setattr(checkpoint, "PreemptionGuard", FakeGuard)
    res = main(argv)
    out = capsys.readouterr().out
    assert "preempted: saved step 1" in out
    assert "(epoch 0 iter 1)" in out
    assert "epoch" not in res              # epoch never completed

    mgr = CheckpointManager(ckpt, track_best=False)
    meta = mgr.metadata()
    mgr.close()
    assert meta == {"epoch": 0, "resume_it": 1, "iters_per_epoch": 3,
                    "global_batch": 8, "world": 1}   # batch 1 x 8 devices

    monkeypatch.undo()                     # real (never-fired) guard
    res2 = main(argv)
    out = capsys.readouterr().out
    assert "auto-resumed from epoch 0 iter 1" in out
    assert res2["epoch"] == 0
    assert math.isfinite(res2["train_loss"])


def test_resnet50_preempt_geometry_change_restarts_epoch(tmp_path, capsys,
                                                         monkeypatch):
    """resume_it is only exact for identical iteration geometry; when
    --max-batches-per-epoch changes after a preemption, the interrupted
    epoch restarts from iter 0 instead of mis-indexing the sampler."""
    from cpd_tpu.train import checkpoint
    from resnet50.main import main

    FakeGuard = _make_fake_guard(1)

    ckpt = str(tmp_path / "ck")
    base = ["--batch-size", "1", "--epochs", "1", "--arch", "tiny",
            "--num-classes", "10", "--image-size", "32", "--grad_exp", "5",
            "--grad_man", "2", "--checkpoint-dir", ckpt,
            "--log-dir", str(tmp_path / "logs"), "--mode", "fast"]
    monkeypatch.setattr(checkpoint, "PreemptionGuard", FakeGuard)
    main(base + ["--max-batches-per-epoch", "3"])
    capsys.readouterr()

    monkeypatch.undo()
    res = main(base + ["--max-batches-per-epoch", "2"])
    out = capsys.readouterr().out
    assert "iteration geometry changed" in out
    assert "auto-resumed from epoch 0" in out
    assert res["epoch"] == 0


def test_resnet18_preempt_saves_and_resumes(tmp_path, tiny_cifar, capsys,
                                            monkeypatch):
    """Iteration-based trainer: preempt at iter 2, resume at exactly 2."""
    from cpd_tpu.train import checkpoint
    from resnet18_cifar.train import main

    FakeGuard = _make_fake_guard(2)

    argv = ["--arch", "tiny", "--max-iter", "4", "--batch_size", "2",
            "--val_freq", "4", "--data-root", tiny_cifar,
            "--save_path", str(tmp_path / "ck"), "--mode", "fast"]
    monkeypatch.setattr(checkpoint, "PreemptionGuard", FakeGuard)
    res = main(argv)
    out = capsys.readouterr().out
    assert "preempted: saved iter 2" in out
    assert res["step"] == 2

    monkeypatch.undo()
    res2 = main(argv)
    out = capsys.readouterr().out
    assert "resumed from iter 2" in out
    assert res2["step"] == 4


def test_resnet50_trainer_on_committed_imagefolder(tmp_path):
    """The FLAGSHIP trainer's real-data path on COMMITTED bytes (round
    5): --train-dir points at the in-repo ImageFolder fixture, so the
    PIL decode + RandomResizedCrop + center-crop val pipeline runs on
    files the process did not fabricate — the ImageNet analog of the
    CIFAR canary."""
    from resnet50.main import main

    fixture = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "fixtures", "imagenet_folder")
    res = main(["--train-dir", fixture, "--batch-size", "1",
                "--epochs", "1", "--arch", "tiny", "--num-classes", "10",
                "--max-batches-per-epoch", "2", "--image-size", "32",
                "--use-APS", "--grad_exp", "5", "--grad_man", "2",
                "--checkpoint-dir", str(tmp_path / "ck"),
                "--log-dir", str(tmp_path / "logs"), "--mode", "fast"])
    assert res["epoch"] == 0
    assert math.isfinite(res["train_loss"])
    assert math.isfinite(res["val_loss"])


def test_resnet50_trainer_zero1_smoke(tmp_path):
    """--zero1 shards the momentum 1/N over dp through the flagship CLI."""
    from resnet50.main import main

    res = main(["--batch-size", "1", "--epochs", "1", "--arch", "tiny",
                "--num-classes", "10", "--max-batches-per-epoch", "2",
                "--image-size", "32", "--use-APS", "--grad_exp", "5",
                "--grad_man", "2", "--zero1",
                "--checkpoint-dir", str(tmp_path / "ck"),
                "--log-dir", str(tmp_path / "logs"), "--mode", "fast"])
    assert res["epoch"] == 0
    assert math.isfinite(res["train_loss"])


def test_resnet50_trainer_zero3_smoke(tmp_path):
    """--zero3 shards params+momentum+reduction 1/N over dp through the
    flagship CLI, including the unpacked-eval validation path."""
    from resnet50.main import main

    res = main(["--batch-size", "1", "--epochs", "1", "--arch", "tiny",
                "--num-classes", "10", "--max-batches-per-epoch", "2",
                "--image-size", "32", "--use-APS", "--grad_exp", "5",
                "--grad_man", "2", "--zero3",
                "--checkpoint-dir", str(tmp_path / "ck"),
                "--log-dir", str(tmp_path / "logs"), "--mode", "faithful"])
    assert res["epoch"] == 0
    assert math.isfinite(res["train_loss"])
    assert math.isfinite(res["val_loss"])


def test_resnet18_trainer_zero2_lars_smoke(tiny_cifar, tmp_path, capsys):
    """--zero2 + --use_lars through the LARS-recipe CLI (round 5): the
    sharded faithful reduction AND sharded per-layer trust ratios, end
    to end with APS."""
    from resnet18_cifar.train import main

    res = main(["--use_APS", "--grad_exp", "5", "--grad_man", "2",
                "--use_lars", "--zero2", "--arch", "tiny",
                "--data-root", tiny_cifar, "--max-iter", "4",
                "--batch_size", "2", "--val_freq", "4",
                "--save_path", str(tmp_path / "ck"), "--mode",
                "faithful"])
    assert math.isfinite(res["best_prec1"])
    out = capsys.readouterr().out
    assert "All Loss" in out


def test_resnet18_trainer_resume_continues_training(tiny_cifar, tmp_path):
    """Auto-resume must REPLICATE the orbax-restored state back onto the
    mesh and keep training — restore committed the arrays to one device,
    which crashed the sharded step (round-2 regression)."""
    from resnet18_cifar.train import main

    save = str(tmp_path / "ckpt")
    common = ["--arch", "tiny", "--data-root", tiny_cifar,
              "--batch_size", "2", "--val_freq", "100",
              "--save_path", save, "--mode", "fast"]
    res1 = main(common + ["--max-iter", "2"])
    assert res1["step"] == 2
    res2 = main(common + ["--max-iter", "4"])   # resumes at 2, trains 2 more
    assert res2["step"] == 4
    assert math.isfinite(res2["loss"])


def test_fcn_trainer_on_committed_cityscapes_tree(tmp_path):
    """The FCN trainer's real-data path on COMMITTED bytes (round 5):
    --data-root points at the in-repo leftImg8bit/gtFine fixture —
    completing the committed-real-format trio (CIFAR, ImageNet
    ImageFolder, Cityscapes)."""
    from fcn.train import main

    fixture = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "fixtures", "cityscapes_tree")
    res = main(["--crop-size", "32", "--batch-size", "1", "--data-root",
                fixture, "--tiny-backbone", "--use_APS", "--grad_exp",
                "5", "--grad_man", "2", "--max-iter", "2", "--val-freq",
                "2", "--save-path", str(tmp_path / "fcn"),
                "--mode", "fast"])
    assert res["step"] == 2
    assert math.isfinite(res["loss"])
    assert 0.0 <= res["val_pix_acc"] <= 1.0


def test_fcn_trainer_smoke(tmp_path):
    from fcn.train import main

    # faithful mode + aux head + REAL-format Cityscapes tree: stage-3
    # auxiliary loss through the full quantized pipeline, fed by the
    # leftImg8bit/gtFine walker (19 trainId classes)
    root = _write_tiny_cityscapes(str(tmp_path / "cs"))
    common = ["--crop-size", "32", "--batch-size", "1", "--data-root", root,
              "--tiny-backbone", "--aux-head", "--use_APS",
              "--grad_exp", "5", "--grad_man", "2", "--ckpt-freq", "2",
              "--save-path", str(tmp_path / "fcn"), "--mode", "faithful"]
    res = main(common + ["--max-iter", "2", "--val-freq", "2"])
    assert res["step"] == 2
    assert math.isfinite(res["loss"])
    assert 0.0 <= res["accuracy"] <= 1.0
    # periodic seg evaluation ran (mmseg EvalHook parity): pixAcc + mIoU
    assert 0.0 <= res["val_pix_acc"] <= 1.0
    assert 0.0 <= res["val_miou"] <= 1.0
    # interval checkpoint written; a second invocation must drive FCN's
    # OWN restore -> replicate wiring (train.py keeps its own copy of
    # that block, so the resnet18/resnet50 resume tests don't cover it).
    # No --val-freq: the resumed run has 0 iters left and must not pay
    # the eval-graph compile again.
    res2 = main(common + ["--max-iter", "2"])
    assert res2["step"] == 2 and "loss" not in res2


def test_draw_curve_parses_both_formats(tmp_path):
    import draw_curve

    log = tmp_path / "aps.log"
    log.write_text("noise\n * All Loss 1.2345 Prec@1 55.000 Prec@5 90.000\n"
                   " * All Loss 1.1000 Prec@1 60.000 Prec@5 92.000\n")
    assert draw_curve.parse_stdout_log(str(log)) == [55.0, 60.0]

    jsonl = tmp_path / "scalars.jsonl"
    jsonl.write_text(json.dumps({"tag": "val/top1", "step": 1,
                                 "value": 0.5}) + "\n" +
                     json.dumps({"tag": "train/loss", "step": 1,
                                 "value": 2.0}) + "\n")
    assert draw_curve.parse_jsonl(str(jsonl)) == [50.0]

    out = tmp_path / "c.png"
    draw_curve.main([str(log), str(jsonl), "-o", str(out)])
    assert out.is_file()


def test_synthetic_imagenet_determinism():
    from cpd_tpu.data.imagenet import SyntheticImageNet

    ds = ds2 = None
    ds = SyntheticImageNet(16, num_classes=10, size=8, seed=3)
    ds2 = SyntheticImageNet(16, num_classes=10, size=8, seed=3)
    x1, y1 = ds.batch([0, 5, 7])
    x2, y2 = ds2.batch([0, 5, 7])
    np.testing.assert_array_equal(y1, y2)
    np.testing.assert_array_equal(x1, x2)
    assert x1.shape == (3, 8, 8, 3)


def test_image_folder_dataset(tmp_path):
    from PIL import Image

    from cpd_tpu.data.imagenet import ImageFolderDataset

    rng = np.random.RandomState(0)
    for cls in ("cat", "dog"):
        d = tmp_path / cls
        d.mkdir()
        for i in range(2):
            arr = rng.randint(0, 255, size=(40, 48, 3), dtype=np.uint8)
            Image.fromarray(arr).save(d / f"{i}.png")
    ds = ImageFolderDataset(str(tmp_path), size=16, train=True)
    assert len(ds) == 4
    assert ds.class_to_idx == {"cat": 0, "dog": 1}
    x, y = ds.batch([0, 3], seed=1)
    assert x.shape == (2, 16, 16, 3)
    assert list(y) == [0, 1]
    # eval path: deterministic center crop
    ev = ImageFolderDataset(str(tmp_path), size=16, train=False)
    x1, _ = ev.batch([1])
    x2, _ = ev.batch([1])
    np.testing.assert_array_equal(x1, x2)


def _write_tiny_cityscapes(root, n_imgs=3, h=64, w=96):
    """Real-format leftImg8bit/gtFine fixture tree (two cities)."""
    from PIL import Image

    rng = np.random.RandomState(0)
    for city_i, city in enumerate(("aaa", "bbb")):
        for k in range(n_imgs):
            stem = f"{city}_{k:06d}_000019"
            img_dir = os.path.join(root, "leftImg8bit", "train", city)
            lab_dir = os.path.join(root, "gtFine", "train", city)
            os.makedirs(img_dir, exist_ok=True)
            os.makedirs(lab_dir, exist_ok=True)
            img = rng.randint(0, 256, (h, w, 3), dtype=np.uint8)
            # raw labelIds: road(7), car(26), sky(23) bands + void(0) strip
            lab = np.zeros((h, w), np.uint8)
            lab[: h // 3] = 23
            lab[h // 3: 2 * h // 3] = 7
            lab[2 * h // 3:] = 26
            lab[:, : w // 3] = 0                # void -> ignore
            Image.fromarray(img).save(
                os.path.join(img_dir, stem + "_leftImg8bit.png"))
            Image.fromarray(lab).save(
                os.path.join(lab_dir, stem + "_gtFine_labelIds.png"))
    return root


def test_cityscapes_loader_real_tree(tmp_path):
    from cpd_tpu.data.segmentation import (CITYSCAPES_IGNORE,
                                           CityscapesDataset,
                                           load_segmentation)

    root = _write_tiny_cityscapes(str(tmp_path))
    ds = load_segmentation(root, crop_size=48)
    assert isinstance(ds, CityscapesDataset)
    assert len(ds) == 6
    x, y = ds.batch([0, 3, 5], seed=1)
    assert x.shape == (3, 48, 48, 3) and x.dtype == np.float32
    assert y.shape == (3, 48, 48) and y.dtype == np.int32
    # labelId -> trainId: only {sky=10, road=0, car=13, ignore} can appear
    assert set(np.unique(y)) <= {0, 10, 13, CITYSCAPES_IGNORE}
    assert CITYSCAPES_IGNORE in np.unique(y)    # the void strip
    # normalized pixels are z-scores, not raw bytes
    assert np.abs(x).max() < 5.0
    # determinism under the (seed, index) contract
    x2, y2 = ds.batch([0, 3, 5], seed=1)
    np.testing.assert_array_equal(x, x2)
    np.testing.assert_array_equal(y, y2)
    # different seed -> different crops somewhere
    x3, _ = ds.batch([0, 3, 5], seed=2)
    assert not np.array_equal(x, x3)


def test_cityscapes_loader_pads_small_images(tmp_path):
    from cpd_tpu.data.segmentation import (CITYSCAPES_IGNORE,
                                           CityscapesDataset)

    root = _write_tiny_cityscapes(str(tmp_path), h=32, w=40)
    ds = CityscapesDataset(root, crop_size=64, flip=False)
    x, y = ds.batch([0], seed=0)
    # padded region: ignore labels, zero pixels
    assert np.all(y[0, 32:, :] == CITYSCAPES_IGNORE)
    assert np.all(x[0, 32:, :, :] == 0.0)
    assert np.any(y[0, :32, :40] != CITYSCAPES_IGNORE)


def test_load_segmentation_explicit_root_is_strict(tmp_path):
    """No root -> synthetic stand-in; an EXPLICIT root with no Cityscapes
    tree raises (a typo'd --data-root must not silently train on
    synthetic data — QUICKSTART.md contract)."""
    from cpd_tpu.data.segmentation import (SyntheticSegmentation,
                                           load_segmentation)

    ds = load_segmentation(None, crop_size=32, synthetic_size=8)
    assert isinstance(ds, SyntheticSegmentation)
    assert len(ds) == 8
    with pytest.raises(FileNotFoundError):
        load_segmentation(str(tmp_path / "nope"), crop_size=32)


def test_seg_loss_ignores_ignore_label():
    import jax.numpy as jnp

    from cpd_tpu.train import seg_cross_entropy_loss

    loss_fn = seg_cross_entropy_loss(ignore_label=255)
    logits = jnp.zeros((1, 2, 2, 3))
    labels = jnp.array([[[0, 255], [255, 255]]])
    # only one valid pixel, uniform logits -> CE = log(3)
    assert np.isclose(float(loss_fn(logits, labels)), np.log(3), atol=1e-6)


def test_lm_trainer_smoke(tmp_path):
    from lm.train import main

    argv = ["--dp", "2", "--sp", "2", "--tp", "2", "--seq-len", "32",
            "--d-model", "32", "--n-layers", "2", "--n-heads", "4",
            "--vocab-size", "64", "--batch-size", "2", "--max-iter", "3",
            "--use_APS", "--grad_exp", "5", "--grad_man", "2",
            "--ckpt-freq", "3", "--sample", "4",
            "--save-path", str(tmp_path / "lm"), "--mode", "faithful"]
    res = main(argv)
    assert res["step"] == 3
    assert math.isfinite(res["loss"])
    # --sample decoded 4 new tokens from an 8-token prompt
    assert len(res["sample"]) == 12
    assert all(0 <= t < 64 for t in res["sample"])
    # sharded-state checkpoint written; auto-resume restores and re-lays
    # it out over the dp x sp x tp mesh (0 iters left)
    res2 = main(argv)
    assert res2["step"] == 3 and "loss" not in res2


def test_lm_trainer_flash_gqa_pallas_bwd_reaches_kernel(tmp_path,
                                                       monkeypatch):
    """--attn-impl flash --n-kv-heads must actually route through the GQA
    flash kernel, and its gradient through the Pallas backward
    kernels' call, with no flag to ask for them — regression for the round-5
    indentation slip that left `model_kw.update(attn_impl=...)` stranded
    after a raise, silently training with xla attention while the flags
    validated clean."""
    import sys

    import cpd_tpu.ops.flash_gqa  # noqa: F401
    fg_mod = sys.modules["cpd_tpu.ops.flash_gqa"]
    from lm.train import main

    calls, bwd_calls = [], []
    real, real_bwd = fg_mod.flash_gqa, fg_mod._flash_gqa_bwd_call

    def spy(q, k, v, causal=True):
        calls.append((q.shape[2], k.shape[2]))
        return real(q, k, v, causal)

    def spy_bwd(q, k, v, *rest):
        bwd_calls.append((q.shape[2], k.shape[2]))
        return real_bwd(q, k, v, *rest)

    monkeypatch.setattr(fg_mod, "flash_gqa", spy)
    monkeypatch.setattr(fg_mod, "_flash_gqa_bwd_call", spy_bwd)
    res = main(["--dp", "8", "--seq-len", "16", "--d-model", "32",
                "--n-layers", "1", "--n-heads", "4", "--n-kv-heads", "2",
                "--attn-impl", "flash",
                "--vocab-size", "32", "--batch-size", "2",
                "--max-iter", "2", "--save-path", str(tmp_path / "lm")])
    assert math.isfinite(res["loss"])
    assert calls and all(c == (4, 2) for c in calls), calls
    assert bwd_calls and all(c == (4, 2) for c in bwd_calls), bwd_calls


def test_lm_trainer_pp_and_moe_paths(tmp_path):
    """--pp and --moe switch the trainer onto the pipeline / expert
    parallel step builders (GPipe streaming, all_to_all dispatch)."""
    from lm.train import main

    common = ["--seq-len", "32", "--d-model", "32", "--n-layers", "2",
              "--n-heads", "4", "--vocab-size", "64", "--batch-size", "4",
              "--max-iter", "2", "--val-freq", "2", "--ckpt-freq", "99",
              "--use_APS", "--grad_exp", "5", "--grad_man", "2"]
    r = main(common + ["--dp", "4", "--pp", "2",
                       "--save-path", str(tmp_path / "pp")])
    assert r["step"] == 2 and math.isfinite(r["loss"])
    r = main(common + ["--dp", "4", "--moe", "--ep", "2",
                       "--n-experts", "4",
                       "--save-path", str(tmp_path / "moe")])
    assert r["step"] == 2 and math.isfinite(r["loss"])


def test_load_cifar10_explicit_root_is_strict(tiny_cifar, tmp_path):
    """Explicit root: real tree loads, missing tree raises (never a silent
    synthetic fallback — QUICKSTART.md contract)."""
    from cpd_tpu.data.cifar import load_cifar10

    tx, ty, vx, vy = load_cifar10(tiny_cifar)
    assert tx.shape == (510, 32, 32, 3) and tx.dtype == np.uint8
    assert len(vy) == 64
    with pytest.raises(FileNotFoundError):
        load_cifar10(str(tmp_path / "nope"))


def test_load_imagenet_explicit_root_is_strict(tmp_path):
    from cpd_tpu.data.imagenet import load_imagenet

    with pytest.raises(FileNotFoundError):
        load_imagenet(str(tmp_path / "nope"))


def test_resnet50_trainer_vit_arch(tmp_path):
    """--arch vit: the registry's uniform model contract lets the ImageNet
    trainer drive the ViT family through the same quantized APS step."""
    from resnet50.main import main

    res = main(["--batch-size", "1", "--epochs", "1", "--arch", "vit",
                "--num-classes", "10", "--max-batches-per-epoch", "2",
                "--image-size", "32", "--use-APS", "--grad_exp", "5",
                "--grad_man", "2", "--checkpoint-dir",
                str(tmp_path / "ck"), "--log-dir", str(tmp_path / "logs"),
                "--mode", "faithful"])
    assert res["epoch"] == 0
    assert math.isfinite(res["train_loss"])
    assert not res["diverged"]
