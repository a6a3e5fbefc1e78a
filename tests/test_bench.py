"""bench.py off the chip: it must refuse to time anything, report an
unknown device kind as an error, and let a failing phase fail the run."""

import types

import pytest


@pytest.fixture()
def bench_mod():
    import bench
    return bench


def _fake_tpu(monkeypatch, kind="TPU v5 lite"):
    import jax
    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    return dev


def test_refuses_non_tpu_backend_before_computing(bench_mod, monkeypatch,
                                                  capsys):
    def never(*a, **k):
        raise AssertionError("run_bench reached on a non-TPU backend")

    monkeypatch.setattr(bench_mod, "run_bench", never)
    with pytest.raises(SystemExit) as exit_info:
        bench_mod.main()                 # conftest pins the cpu platform
    assert exit_info.value.code == 2
    captured = capsys.readouterr()
    assert "'cpu'" in captured.err and "needs a TPU" in captured.err
    assert captured.out == ""            # no JSON, no value


def test_unknown_device_kind_is_an_error_not_a_default(bench_mod,
                                                       monkeypatch):
    assert bench_mod.peak_tflops("TPU v5 lite") == 197.0
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        bench_mod.peak_tflops("TPU v9 imaginary")
    # and run_bench asks the table before it builds or times anything
    dev = _fake_tpu(monkeypatch, kind="TPU v9 imaginary")
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        bench_mod.run_bench([dev])


def test_a_failing_phase_fails_the_run(bench_mod, monkeypatch, capsys):
    _fake_tpu(monkeypatch)

    def boom(*a, **k):
        raise RuntimeError("phase blew up")

    monkeypatch.setattr(bench_mod, "run_bench", boom)
    with pytest.raises(RuntimeError, match="phase blew up"):
        bench_mod.main()                 # -> traceback, nonzero exit
    assert capsys.readouterr().out == ""


def test_measure_ends_every_window_in_block_until_ready(bench_mod):
    blocked = []
    fake_jax = types.SimpleNamespace(
        block_until_ready=lambda tree: blocked.append(tree))
    calls = []

    def step(state, x, y):
        calls.append(state)
        return state + 1, {"loss": 0.0}

    best, median, state = bench_mod._measure(
        fake_jax, step, 0, None, None, iters=6, windows=3, imgs_per_call=4)
    assert len(calls) == 1 + 6           # warm-up + 3 windows x 2 calls
    assert len(blocked) == 1 + 3         # warm-up + once per window
    assert state == 7 and best >= median > 0


def test_no_handler_that_continues():
    import inspect

    import bench
    import chip_smoke

    for mod in (bench, chip_smoke):
        assert "except Exception" not in inspect.getsource(mod)
