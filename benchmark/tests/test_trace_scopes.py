"""The reader of the trace's per-operation metadata and the reduction by
scope: the decoder against a hand-built XSpace, the scope paths, the
exposed-collective arithmetic on synthetic intervals, the readers, and
the whole chain pinned on recorded pieces of real v5e traces."""

from __future__ import annotations

import json
import os
import re
import struct
import types

import pytest

import conftest  # noqa: F401  (platform and path)
from benchmark import run, trace_reduce, trace_scopes
from benchmark.readers import scopes as readers

HERE = os.path.dirname(os.path.abspath(__file__))


# ------------------------------------------------- a protobuf, by hand

def varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, float):
        return varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def xstat(metadata_id: int, value) -> bytes:
    kind = {int: 3, float: 2, str: 5}[type(value)]
    return field(1, metadata_id) + field(kind, value)


def xplane(name: str, stat_names: dict, events: dict, lines=b"") -> bytes:
    """stat_names: {id: name}; events: {id: (name, [XStat bytes])}."""
    body = field(2, name) + lines
    for eid, (ename, stats) in events.items():
        meta = field(1, eid) + field(2, ename) + b"".join(
            field(5, s) for s in stats)
        body += field(4, field(1, eid) + field(2, meta))
    for sid, sname in stat_names.items():
        body += field(5, field(1, sid) + field(
            2, field(1, sid) + field(2, sname)))
    return body


def test_decoder_reads_event_metadata_of_device_planes(tmp_path):
    names = {1: "tf_op", 2: "flops", 3: "bytes_accessed", 4: "hlo_category",
             5: "shape_with_layout", 6: "source", 7: "a string by reference"}
    dev0 = xplane("/device:TPU:0", names, {
        10: ("%fusion.1 = f32[8]{0} fusion(...), kind=kLoop",
             [xstat(1, "jit(step)/cpd.reduce/aps.scale/mul:"), xstat(2, 16),
              xstat(3, 64), xstat(4, "loop fusion"),
              xstat(5, "f32[8]{0}"), xstat(6, "aps.py:1")]),
        11: ("%all-gather.2 = u8[4,8]{1,0} all-gather(...)",
             # a string stat that points into stat_metadata
             [field(1, 1) + field(7, 7), xstat(3, 32)]),
    }, lines=field(3, b"\x08\x01" + field(2, "XLA Ops")))   # skipped
    dev1 = xplane("/device:TPU:1", names, {
        10: ("%fusion.1 = f32[8]{0} fusion(...), kind=kLoop",
             [xstat(1, "jit(step)/cpd.optimizer/add:"), xstat(2, 1.5)])})
    host = xplane("/host:CPU", names, {1: ("dispatch", [])})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, dev0) + field(1, dev1) + field(1, host)
                     + field(4, "hostname"))
    md = trace_scopes.load_metadata(str(path))
    assert sorted(md) == ["0", "1"]           # the host plane is not a device
    fusion = md["0"]["%fusion.1 = f32[8]{0} fusion(...), kind=kLoop"]
    assert fusion == {"scope": "jit(step)/cpd.reduce/aps.scale/mul:",
                      "flops": 16, "bytes_accessed": 64,
                      "category": "loop fusion", "shape": "f32[8]{0}"}
    gather = md["0"]["%all-gather.2 = u8[4,8]{1,0} all-gather(...)"]
    assert gather == {"scope": "a string by reference", "bytes_accessed": 32}
    assert md["1"]["%fusion.1 = f32[8]{0} fusion(...), kind=kLoop"] == {
        "scope": "jit(step)/cpd.optimizer/add:", "flops": 1.5}


# --------------------------------------------------------- scope paths

@pytest.mark.parametrize("stack, path", [
    ("jit(step)/cpd.loss_grad/jvp(Model)/dot_general:", "cpd.loss_grad"),
    ("jit(step)/cpd.loss_grad/transpose(jvp(Model))/mul:",
     "cpd.loss_grad@bwd"),
    ("jit(step)/cpd.loss_grad/while/body/jvp(Model)/kernel.k/pallas_call:",
     "cpd.loss_grad/kernel.k"),
    ("jit(s)/cpd.loss_grad/transpose(jvp(M))/checkpoint/kernel.k/x:",
     "cpd.loss_grad/kernel.k@bwd"),
    # a reduction run inside the backward pass is the reduction's
    ("jit(s)/cpd.loss_grad/transpose(cpd.loss_grad)/jvp(cpd.reduce)"
     "/aps.max_exp/reduce_max:", "cpd.loss_grad/cpd.reduce/aps.max_exp"),
    ("jit(s)/cpd.reduce/jit(unpack_exmy)/wire.unpack/and:",
     "cpd.reduce/wire.unpack"),
    ("jit(s)/cpd.reduce/reduce.scan/reduce.scan/while/body/add:",
     "cpd.reduce/reduce.scan"),
    ("jit(s)/cpd.optimizer/cpd.reduce/wire.collective/psum:",
     "cpd.optimizer/cpd.reduce/wire.collective"),
    ("jit(unpack_exmy)/wire.unpack/and:", "unscoped"),  # no owner
    ("jit(step)/shard_map/add:", "unscoped"),
    ("", "unscoped"),
])
def test_scope_path(stack, path):
    assert trace_scopes.scope_path(stack) == path


def test_shapes_and_received_bytes():
    assert trace_scopes.shape_bytes("u8[4,100]{1,0:T(4,128)(4,1)}") == (
        400.0, ["u8"])
    assert trace_scopes.shape_bytes(
        "(bf16[2,8]{1,0}, f32[]{:S(2)}, pred[3]{0})") == (
            39.0, ["bf16", "f32", "pred"])
    gather = trace_scopes.received_bytes("all-gather.3", "u8[4,100]{1,0}", 4)
    assert gather == 300.0                       # (W-1)/W of the output
    assert trace_scopes.received_bytes(
        "collective-permute.1", "u8[100]{0}", 4) == 100.0
    assert trace_scopes.received_bytes(
        "all-reduce.7", "f32[10]{0}", 4) == 60.0  # 2(W-1)/W x 40


# ------------------------------------------- exposed collective time

@pytest.mark.parametrize("others, exposed", [
    ([[0, 100]], 0.0),                    # fully hidden
    ([[0, 10], [60, 100]], 40.0),         # fully exposed
    ([[0, 30]], 20.0),                    # half
    ([[0, 15], [25, 35], [45, 100]], 10.0 + 10.0),
    ([], 40.0),
])
def test_exposed_time_on_synthetic_intervals(others, exposed):
    collectives = [[10, 50]]
    assert trace_scopes.exposed_ns(collectives, others) == exposed


def synthetic():
    """Two devices; device 0 runs a scoped fusion [100, 130), an
    all-gather under `wire.collective` [130, 170) and an unnamed copy
    [180, 200): operations of one line never overlap."""
    gather = "%all-gather.1 = u8[2,64]{1,0} all-gather(...)"
    fusion = "%fusion.1 = f32[8]{0} fusion(...), kind=kLoop"
    copy = "%copy.1 = f32[8]{0} copy(...)"
    ops = [[fusion, 100, 30], [gather, 130, 40], [copy, 180, 20]]
    tables = {"devices": {"0": {"ops": ops}, "1": {"ops": ops}},
              "host": [["dispatch", 100, 10], ["wait", 110, 90]]}
    meta = {"0": {
        gather: {"scope": "jit(s)/cpd.reduce/wire.collective/all_gather:",
                 "bytes_accessed": 256, "shape": "u8[2,64]{1,0}"},
        fusion: {"scope": "jit(s)/cpd.reduce/aps.scale/mul:", "flops": 8,
                 "bytes_accessed": 64},
        copy: {}}}
    return tables, meta


def test_reduce_scopes_on_a_synthetic_trace():
    tables, meta = synthetic()
    r = trace_scopes.reduce_scopes(tables, meta)
    assert r["scopes_found"] and r["world"] == 2
    by = r["by_scope"]
    assert by["cpd.reduce/wire.collective"]["s"] == pytest.approx(40e-9)
    assert by["cpd.reduce/aps.scale"] == {
        "s": pytest.approx(30e-9), "flops": 8.0, "bytes": 64.0, "calls": 1}
    assert by["unscoped"]["s"] == pytest.approx(20e-9)
    assert r["busy_s"] == pytest.approx(90e-9)
    c = r["collectives"]
    assert c["exposed_s"] == pytest.approx(40e-9)    # nothing hides it
    assert c["received_bytes"] == 64.0 and c["dtypes"] == ["u8"]
    assert c["calls"] == 1
    # an async collective's start-to-done span is collective time too:
    # [115, 190) adds [170, 180), the rest is hidden or counted already
    r = trace_scopes.reduce_scopes(tables, meta, {"0": [
        ["%all-gather-start.9 = (...) all-gather-start(...)", 115, 75]]})
    assert r["collectives"]["exposed_s"] == pytest.approx(50e-9)

    # a program without scopes: found nothing, and the readers say nothing
    bare = {"0": {k: {} for k in meta["0"]}}
    r = trace_scopes.reduce_scopes(tables, bare)
    assert not r["scopes_found"]
    ctx = {"scopes": r, "window": types.SimpleNamespace(steps=1)}
    assert readers.ms_per_step(ctx, {"include": "."}) is None
    assert readers.unscoped_pct(ctx, {}) is None
    assert readers.unscoped_pct({"scopes": None}, {}) is None


# ------------------------------------------------------------- readers

def test_readers_on_a_synthetic_table():
    tables, meta = synthetic()
    ctx = {"scopes": trace_scopes.reduce_scopes(tables, meta),
           "window": types.SimpleNamespace(steps=2), "chips": 2}
    assert readers.ms_per_step(
        ctx, {"include": r"(^|/)cpd\.reduce(/|$)",
              "exclude": r"/wire\.collective"}) == pytest.approx(15e-6)
    assert readers.gbytes_per_step(
        ctx, {"include": r"aps\.scale"}) == pytest.approx(32e-9)
    assert readers.unscoped_pct(ctx, {}) == pytest.approx(100 * 20 / 90)
    assert readers.exposed_collective_ms_per_step(
        ctx, {}) == pytest.approx(20e-6)
    assert readers.collective_bytes_per_step(ctx, {}) == 32.0


def test_roofline_reader_and_the_attention_count():
    from benchmark.flops import attention
    config = {"num_attention_heads": 4, "num_key_value_heads": 2,
              "hidden_size": 64, "num_hidden_layers": 3,
              "model_kwargs": {"dtype": "bfloat16"}}
    traffic = {"batch_per_chip": 2, "seq_len": 128}
    ops, nbytes = attention.flash_fwd(config, traffic)
    assert ops == 3 * 2 * 2 * 2 * 4 * 128 * 128 * 16 / 2
    assert nbytes == 3 * (2 * 2 * 128 * 16 * (8 + 4) + 4 * 2 * 4 * 128)
    scopes = {"scopes_found": True, "by_scope": {
        "cpd.loss_grad/kernel.flash_gqa_fwd": {"s": 2e-6, "bytes": 0.0,
                                               "flops": 0.0, "calls": 3}}}
    ctx = {"scopes": scopes, "window": types.SimpleNamespace(steps=1),
           "config": config, "traffic": traffic,
           "peaks": {"bf16_tflops": 100.0, "hbm_gbytes_per_s": 1000.0}}
    args = {"include": r"(^|/)kernel\.flash_gqa_fwd(@bwd)?$",
            "ops": "attention:flash_fwd"}
    floor = max(ops / 100e12, nbytes / 1000e9)
    assert readers.roofline_pct(ctx, args) == pytest.approx(
        100 * floor / 2e-6)
    assert readers.roofline_pct(ctx, {**args, "include": "nothing"}) is None


# --------------------------- the per_layer entries that read the scopes

def scope_entries():
    """(entry of BENCHMARK.json, its metric file) for every per_layer
    metric that a `readers/scopes.py` function reads."""
    with open(os.path.join(conftest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = []
    for e in bench["per_layer"]:
        spec = run.load_json(run.HERE, "metrics", e["name"] + ".json")
        if spec["reader"].startswith("scopes:"):
            out.append((e, spec))
    return bench, out


def test_scope_entries_keep_to_benchmark_jsons_rules():
    """The ten entries PR 25 staged are BENCHMARK.json's now: each has
    its metric file, a reader in `readers/scopes.py`, regular expressions
    that compile, and lists only cells that exist; the staging file is
    gone and nothing reads it."""
    bench, entries = scope_entries()
    cells = {w["name"] for w in bench["workloads"]}
    assert len(entries) == len({e["name"] for e, _ in entries}) >= 10
    assert not os.path.exists(os.path.join(run.HERE, "scopes_per_layer.json"))
    for e, spec in entries:
        assert e["source"] == "device_trace"
        assert e["moves"] == "train_rate_per_chip"
        assert set(e.get("workloads", [])) <= cells
        assert callable(run.resolve(spec["reader"], "readers"))
        for key in ("include", "exclude"):
            if key in spec.get("args", {}):
                re.compile(spec["args"][key])
        if "ops" in spec.get("args", {}):
            assert callable(run.resolve(spec["args"]["ops"], "flops"))


METRIC_PATHS = {
    # a model's own scope under `cpd.loss_grad` (any `cpd.*` but the
    # step's five) is still forward or backward
    "step.forward_ms_per_step": (
        ["cpd.loss_grad", "cpd.loss_grad/kernel.flash_gqa_fwd",
         "cpd.loss_grad/cpd.experts", "cpd.loss_grad/cpd.reduced_rank",
         "cpd.loss_grad/cpd.experts/kernel.grouped_gemm"],
        ["cpd.loss_grad@bwd", "cpd.loss_grad/cpd.reduce/aps.scale",
         "cpd.loss_grad/cpd.reduce", "cpd.loss_grad/cpd.experts@bwd",
         "cpd.loss_grad/cpd.experts/cpd.reduce/wire.cast",
         "cpd.optimizer", "cpd.metrics", "unscoped"]),
    "step.backward_ms_per_step": (
        ["cpd.loss_grad@bwd", "cpd.loss_grad/kernel.flash_gqa_bwd_dq@bwd",
         "cpd.loss_grad/cpd.experts@bwd",
         "cpd.loss_grad/cpd.experts/kernel.grouped_gemm@bwd"],
        ["cpd.loss_grad", "cpd.loss_grad/cpd.reduce/wire.cast",
         "cpd.loss_grad/cpd.experts",
         "cpd.loss_grad/cpd.emulate_node/reduce.scan",
         "cpd.loss_grad/cpd.reduce@bwd", "cpd.optimizer@bwd"]),
    "step.optimizer_ms_per_step": (
        ["cpd.optimizer"],
        ["cpd.optimizer/cpd.reduce/wire.collective", "cpd.metrics"]),
    "quant.pipeline_ms_per_step": (
        ["cpd.reduce", "cpd.reduce/aps.max_exp", "cpd.emulate_node",
         "cpd.emulate_node/reduce.scan", "cpd.reduce/wire.unpack",
         "cpd.loss_grad/cpd.reduce/aps.scale",
         "cpd.optimizer/cpd.reduce/reduce.scan",
         "cpd.reduce/kernel.wire_hop"],
        ["cpd.reduce/wire.collective", "cpd.loss_grad", "cpd.optimizer",
         "cpd.metrics", "unscoped"]),
    "quant.cast_ms_per_step": (
        ["cpd.reduce/wire.cast", "cpd.reduce/wire.pack",
         "cpd.loss_grad/cpd.reduce/wire.unpack"],
        ["cpd.reduce/wire.collective", "cpd.reduce/aps.scale",
         "cpd.reduce"]),
    "kernel.flash_gqa_fwd_roofline_pct": (
        ["cpd.loss_grad/kernel.flash_gqa_fwd",
         "cpd.loss_grad/kernel.flash_gqa_fwd@bwd"],
        ["cpd.loss_grad/kernel.flash_gqa_bwd_dq@bwd", "cpd.loss_grad"]),
}


@pytest.mark.parametrize("metric", sorted(METRIC_PATHS))
def test_metric_files_select_the_paths_they_should(metric):
    spec = run.load_json(run.HERE, "metrics", metric + ".json")["args"]
    yes, no = METRIC_PATHS[metric]

    def picked(path):
        return bool(re.search(spec["include"], path)) and not (
            spec.get("exclude") and re.search(spec["exclude"], path))

    assert all(picked(p) for p in yes), [p for p in yes if not picked(p)]
    assert not any(picked(p) for p in no), [p for p in no if picked(p)]


def test_the_pipelines_two_metrics_read_the_same_operations():
    ms = run.load_json(run.HERE, "metrics", "quant.pipeline_ms_per_step.json")
    gb = run.load_json(run.HERE, "metrics",
                       "quant.pipeline_gbytes_per_step.json")
    assert ms["args"] == gb["args"]


# ---------------------------------------- recorded pieces of real traces

def recorded(piece: str) -> dict:
    with open(os.path.join(HERE, "fixtures", "trace_v5e_scopes.json")) as f:
        return json.load(f)[piece]


@pytest.mark.parametrize("piece", ["lm_step", "dp4_step"])
def test_recorded_steps_reduce_to_their_pinned_values(piece):
    fx = recorded(piece)
    r = trace_scopes.reduce_scopes(fx["tables"], fx["metadata"], fx["async"])
    pinned = fx["pinned"]
    assert r["scopes_found"] and r["world"] == pinned["world"]
    assert sorted(r["by_scope"]) == sorted(pinned["by_scope"])
    for path, row in pinned["by_scope"].items():
        assert r["by_scope"][path] == pytest.approx(row, rel=1e-9), path
    for key in ("busy_s", "unscoped_s"):
        assert r[key] == pytest.approx(pinned[key], rel=1e-9)
    assert r["collectives"] == pytest.approx(pinned["collectives"], rel=1e-9)
    # the scopes partition what `trace_reduce` calls busy
    old = trace_reduce.reduce(fx["tables"])
    assert r["busy_s"] == pytest.approx(old["busy_s_first_device"], rel=1e-6)
    assert r["unscoped_s"] < 0.10 * r["busy_s"]


# what the two metric files held before a model's own scopes counted
OLD_FORWARD = r"^cpd\.loss_grad(/kernel\.[^/@]+)*$"
OLD_BACKWARD = r"^cpd\.loss_grad(/kernel\.[^/@]+)*@bwd$"


@pytest.mark.parametrize("piece", ["lm_step", "dp4_step"])
def test_readers_on_the_recorded_steps_through_ctx(piece):
    """What `run.py` does with a trace: the table as `ctx["scopes"]`, the
    readers by their metric files.  The values are the sums of the pinned
    rows that `python -m benchmark.trace_scopes` printed for these traces,
    and the widened forward and backward patterns pick the rows the old
    ones picked."""
    fx = recorded(piece)
    by = fx["pinned"]["by_scope"]
    ctx = {"scopes": trace_scopes.reduce_scopes(fx["tables"], fx["metadata"],
                                                fx["async"]),
           "window": types.SimpleNamespace(steps=1), "chips": 1}

    def read(metric):
        spec = run.load_json(run.HERE, "metrics", metric + ".json")
        return run.resolve(spec["reader"], "readers")(ctx, spec.get("args", {}))

    for metric, old in (("step.forward_ms_per_step", OLD_FORWARD),
                        ("step.backward_ms_per_step", OLD_BACKWARD)):
        rows = [row["s"] for path, row in by.items() if re.search(old, path)]
        assert rows and read(metric) == pytest.approx(1e3 * sum(rows),
                                                      rel=1e-9)
    assert read("step.optimizer_ms_per_step") == pytest.approx(
        1e3 * by["cpd.optimizer"]["s"], rel=1e-9)
    assert read("step.unscoped_pct") == pytest.approx(
        100 * fx["pinned"]["unscoped_s"] / fx["pinned"]["busy_s"], rel=1e-9)
    pipeline = [row for path, row in by.items()
                if re.search(r"(^|/)cpd\.(reduce|emulate_node)", path)
                and "wire.collective" not in path]
    assert read("quant.pipeline_ms_per_step") == pytest.approx(
        1e3 * sum(r["s"] for r in pipeline), rel=1e-9)
    assert read("quant.pipeline_gbytes_per_step") == pytest.approx(
        sum(r["bytes"] for r in pipeline) / 1e9, rel=1e-9)
    assert read("reduce.exposed_collective_ms_per_step") == pytest.approx(
        1e3 * fx["pinned"]["collectives"]["exposed_s"], rel=1e-9)
    assert read("reduce.wire_bytes_measured_per_step") == pytest.approx(
        fx["pinned"]["collectives"]["received_bytes"], rel=1e-9)
    assert trace_scopes.top_scopes(ctx["scopes"], 3) == [
        [path, row["s"]] for path, row in sorted(
            ctx["scopes"]["by_scope"].items(), key=lambda kv: -kv[1]["s"])[:3]]


def test_recorded_lm_step_has_its_layers():
    by = recorded("lm_step")["pinned"]["by_scope"]
    fwd, bwd = by["cpd.loss_grad"]["s"], by["cpd.loss_grad@bwd"]["s"]
    assert 0 < fwd < bwd                  # recomputation sits in backward
    assert by["cpd.loss_grad/kernel.flash_gqa_fwd"]["calls"] == 4
    assert any(p.startswith("cpd.reduce/") for p in by)
    assert "cpd.optimizer" in by


def test_recorded_dp4_step_has_its_collectives():
    fx = recorded("dp4_step")
    c = fx["pinned"]["collectives"]
    assert fx["pinned"]["world"] == 4
    assert "u8" in c["dtypes"] and c["received_bytes"] > 7e7
    assert 0 < c["exposed_s"] <= c["s"] * 1.001
    assert "cpd.reduce/wire.collective" in fx["pinned"]["by_scope"]
