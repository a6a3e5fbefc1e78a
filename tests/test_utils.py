"""Unit tests for cpd_tpu.utils — config merge, loggers, prefetcher,
compile cache.  These are the harness-plumbing pieces every trainer rides
(SURVEY.md §5 config/logging parity); previously only covered indirectly
through the trainer smokes."""

import json
import os
import time

import pytest

from cpd_tpu.obs.timing import now


# ------------------------------------------------------------- config

def test_yaml_merge_cli_precedence(tmp_path):
    import argparse

    from cpd_tpu.utils import load_yaml_config, merge_config_into_args

    cfg = tmp_path / "c.yaml"
    cfg.write_text("common:\n  batch_size: 512\n  arch: res_cifar\n"
                   "  momentum: 0.9\n")
    loaded = load_yaml_config(str(cfg))
    assert loaded["batch_size"] == 512

    args = argparse.Namespace(batch_size=64, arch=None, momentum=None)
    # explicit CLI value (batch_size) beats YAML; None takes the YAML's
    merge_config_into_args(args, loaded,
                           cli_overrides={"batch_size": 64})
    assert args.batch_size == 64
    assert args.arch == "res_cifar"
    assert args.momentum == 0.9


# ------------------------------------------------------------ loggers

def test_table_logger_rank_gate_and_columns(capsys):
    from cpd_tpu.utils import TableLogger

    t = TableLogger(rank=1)
    t.append({"epoch": 1, "loss": 0.5})
    assert capsys.readouterr().out == ""     # non-zero rank is silent

    t0 = TableLogger(rank=0)
    t0.append({"epoch": 1, "loss": 0.5})
    t0.append({"epoch": 2, "loss": 0.25})
    out = capsys.readouterr().out.splitlines()
    assert "epoch" in out[0] and "loss" in out[0]   # header once
    assert len(out) == 3


def test_tsv_logger_dawnbench_format():
    from cpd_tpu.utils import TSVLogger

    tsv = TSVLogger()
    tsv.append({"epoch": 1, "total time": 3600.0, "test acc": 0.9})
    lines = str(tsv).splitlines()
    assert lines[0] == "epoch\thours\ttop1Accuracy"
    epoch, hours, acc = lines[1].split("\t")
    assert epoch == "1" and float(hours) == 1.0 and acc == "90.00"


def test_scalar_writer_jsonl_roundtrip(tmp_path):
    from cpd_tpu.utils import ScalarWriter

    with ScalarWriter(str(tmp_path), rank=0) as w:
        w.add_scalar("train/loss", 1.5, 1)
        w.add_scalar("train/loss", 1.25, 2)
    with ScalarWriter(str(tmp_path / "nope"), rank=1) as w:
        w.add_scalar("train/loss", 9.9, 1)   # rank-gated: no file
    recs = [json.loads(line)
            for line in open(tmp_path / "scalars.jsonl")]
    assert [r["value"] for r in recs] == [1.5, 1.25]
    assert not (tmp_path / "nope").exists()


@pytest.mark.slow  # tensorboard IO; the JSONL logging contract is fast-tier
def test_scalar_writer_tensorboard_events(tmp_path):
    """tensorboard=True mirrors scalars into event files (mix.py:168-171).

    Skips only if no tensorboard backend is importable — this image ships
    one with torch."""
    from cpd_tpu.utils import ScalarWriter

    import pytest
    probe = ScalarWriter._open_tb(str(tmp_path / "probe"))
    if probe is None:
        pytest.skip("no tensorboard backend")
    probe.close()

    with ScalarWriter(str(tmp_path), rank=0, tensorboard=True) as w:
        w.add_scalar("train/loss", 1.5, 1)
    events = [p for p in tmp_path.iterdir()
              if p.name.startswith("events.out.tfevents")]
    assert events, "no TensorBoard event file written"
    assert (tmp_path / "scalars.jsonl").exists()  # JSONL still primary


def test_validation_line_matches_draw_curve_grep():
    from cpd_tpu.utils import format_validation_line

    line = format_validation_line(0.5, 91.25, 99.5)
    # the grep contract of draw_curve.py / reference mix.py:422-425
    assert line.startswith(" * All Loss ")
    assert "Prec@1 91.250" in line and "Prec@5 99.500" in line


# ---------------------------------------------------------- prefetcher

def test_prefetcher_preserves_order_and_exhausts():
    from cpd_tpu.utils.prefetch import Prefetcher

    assert list(Prefetcher(iter(range(20)), depth=3)) == list(range(20))


def test_prefetcher_propagates_source_exception():
    from cpd_tpu.utils.prefetch import Prefetcher

    def bad():
        yield 1
        raise RuntimeError("source broke")

    it = iter(Prefetcher(bad(), depth=2))
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="source broke"):
        for _ in it:
            pass


def test_prefetcher_runs_ahead_of_consumer():
    from cpd_tpu.utils.prefetch import Prefetcher

    produced = []

    def slow_consumer_source():
        for i in range(4):
            produced.append(i)
            yield i

    pf = Prefetcher(slow_consumer_source(), depth=2)
    it = iter(pf)
    first = next(it)
    time.sleep(0.2)                  # give the thread time to run ahead
    assert first == 0
    assert len(produced) >= 2        # producer is ahead of the consumer
    assert list(it) == [1, 2, 3]


def test_prefetcher_next_after_close_raises_stopiteration():
    """Regression: close() drains the queue (discarding the end-of-stream
    sentinel), so a subsequent __next__ used to block forever on the empty
    queue.  A closed prefetcher must read as exhausted, promptly."""
    from cpd_tpu.utils.prefetch import Prefetcher

    pf = Prefetcher(iter(range(100)), depth=2)
    it = iter(pf)
    assert next(it) == 0
    pf.close()
    t0 = now()
    with pytest.raises(StopIteration):
        next(it)
    assert now() - t0 < 2.0   # prompt, not a hang/timeout pile
    with pytest.raises(StopIteration):   # and stays exhausted
        next(it)


def test_prefetcher_close_unblocks_waiting_consumer():
    """A consumer already blocked in __next__ (empty queue, stalled
    producer) must be released by a concurrent close()."""
    import threading

    from cpd_tpu.utils.prefetch import Prefetcher

    gate = threading.Event()

    def stalled():
        yield 0
        gate.wait(10.0)            # producer wedged until the test ends
        yield 1

    pf = Prefetcher(stalled(), depth=1)
    it = iter(pf)
    assert next(it) == 0
    result = {}

    def consume():
        try:
            next(it)
            result["got"] = "item"
        except StopIteration:
            result["got"] = "stop"

    t = threading.Thread(target=consume)
    t.start()
    time.sleep(0.2)                # let the consumer block in __next__
    pf.close()
    t.join(5.0)
    gate.set()
    assert not t.is_alive()
    assert result["got"] == "stop"


# ------------------------------------------------------------- cache

def test_lru_cache_bounds_and_recency():
    from cpd_tpu.utils import LRUCache

    calls = []

    def make(k):
        def create():
            calls.append(k)
            return k * 10
        return create

    c = LRUCache(maxsize=2)
    assert c.get_or_create("a", make("a")) == "a" * 10
    c.get_or_create("b", make("b"))
    c.get_or_create("a", make("a"))      # hit: refreshes recency, no call
    c.get_or_create("c", make("c"))      # evicts b (least recent)
    assert len(c) == 2
    assert "a" in c and "c" in c and "b" not in c
    assert calls == ["a", "b", "c"]
    c.get_or_create("b", make("b"))      # re-creating b is a re-call
    assert calls == ["a", "b", "c", "b"]
    with pytest.raises(ValueError):
        LRUCache(0)


def test_sum_gradients_fn_jit_cache_bounded():
    """make_sum_gradients_fn's per-treedef jit cache must not grow without
    bound when fed many distinct pytree structures — and evicted
    structures must still compute correctly on re-presentation."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cpd_tpu.parallel import make_sum_gradients_fn
    from cpd_tpu.parallel.mesh import data_parallel_mesh

    mesh = data_parallel_mesh()
    fn = make_sum_gradients_fn(mesh, axis_name="dp", grad_exp=8,
                               grad_man=23)
    lru = fn._cache
    w = len(jax.devices())

    def tree(i):
        # i+1 distinct structures: dict with i+1 keys, values a pure
        # function of (i, j) so re-presenting a structure reuses its data
        return {f"k{j}": jnp.asarray(
            np.random.RandomState(i * 100 + j).randn(w, 3)
            .astype(np.float32)) for j in range(i + 1)}

    def place(t):
        return jax.tree.map(lambda g: jax.device_put(
            g, NamedSharding(mesh, P("dp"))), t)

    results = {}
    for i in range(lru.maxsize + 4):     # overflow the bound
        results[i] = fn(place(tree(i)))
    assert len(lru) == lru.maxsize
    # structure 0 was evicted; re-presenting it re-traces and still sums
    again = fn(place(tree(0)))
    np.testing.assert_array_equal(np.asarray(again["k0"]),
                                  np.asarray(results[0]["k0"]))


def test_enable_compile_cache_noop_on_cpu():
    import jax

    from cpd_tpu.utils import enable_compile_cache

    # conftest forces the cpu platform, so this must be a no-op: the
    # XLA:CPU AOT reload of collective executables crashes this jaxlib
    before = jax.config.jax_compilation_cache_dir
    enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before


@pytest.fixture()
def tpu_backend(monkeypatch):
    """Pretend the resolved backend is a TPU so the enabling branch of
    enable_compile_cache runs; every jax.config value it writes is put
    back afterwards."""
    import jax

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def test_compile_cache_dir_from_env_is_left_alone(tpu_backend, monkeypatch,
                                                  tmp_path):
    import jax

    from cpd_tpu.utils import enable_compile_cache

    # jax reads JAX_COMPILATION_CACHE_DIR itself at import; model that
    # state, then check the function sets no directory of its own
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_dir_defaults_to_checkout(tpu_backend, monkeypatch):
    import jax

    from cpd_tpu.utils import default_cache_dir, enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    enable_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert default_cache_dir() == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == default_cache_dir()


def test_default_cache_dir_identical_across_processes():
    # the directory is part of jax's cache key: a path that differed
    # between two processes on one checkout could never hit
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("from cpd_tpu.utils.cache import default_cache_dir; "
            "print(default_cache_dir())")
    outs = [subprocess.run([sys.executable, "-c", code], cwd=repo,
                           capture_output=True, text=True, check=True,
                           timeout=60).stdout.strip() for _ in range(2)]
    assert outs[0] == outs[1] == os.path.join(repo, ".jax_cache")


def test_cache_module_runs_no_machine_code():
    import inspect

    from cpd_tpu.utils import cache

    src = inspect.getsource(cache)
    assert "mmap" not in src and "PROT_EXEC" not in src
