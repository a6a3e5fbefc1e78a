"""`trace_reduce.reduce` on hand-made events, and on a piece of one real
v5e trace kept as a fixture (`fixtures/README.md` says how it was cut)."""

from __future__ import annotations

import json
import os

import pytest

from benchmark import trace_reduce as T

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_v5e_lm_step.json")


def test_union_and_self_times():
    assert T.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    # b nests in a; c follows
    ops = [["%a.1 = x", 0, 10], ["%b.2 = y", 2, 3], ["%c.3 = z", 12, 4]]
    assert T.self_times(ops) == {"%a.1 = x": 7, "%b.2 = y": 3, "%c.3 = z": 4}


def test_names_and_groups():
    line = ("%fusion.14 = (f32[256]{0:T(256)S(1)}, bf16[256,56,56,256]{3,0,2,"
            "1:T(8,128)(2,1)}) fusion(f32[256]{0} %copy-done.574), "
            "kind=kOutput, calls=%fused_computation.48")
    assert T.op_name(line) == "fusion.14"
    assert T.op_group(line) == "fusion kOutput"
    assert T.op_group("%all-gather.3 = u8[4,128]{1,0} all-gather(u8[1,128] "
                      "%x), dimensions={0}") == "all-gather"
    assert T.op_group('%custom-call.7 = bf16[8] custom-call(bf16[8] %q), '
                      'custom_call_target="tpu_custom_call"') == (
                          "custom-call tpu_custom_call")


def test_reduce_on_hand_made_tables():
    tables = {
        "devices": {
            "0": {"ops": [["%m.1 = f32[] fusion(), kind=kOutput", 100, 400],
                          ["%e.2 = f32[] fusion(), kind=kLoop", 600, 200],
                          ["%early.9 = f32[] copy()", 0, 50]]},
            "1": {"ops": [["%m.1 = f32[] fusion(), kind=kOutput", 100,
                           900]]}},
        "host": [["dispatch", 100, 100], ["wait", 200, 900]],
    }
    r = T.reduce(tables)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s_by_device"] == {"0": pytest.approx(600e-9),
                                     "1": pytest.approx(900e-9)}
    assert r["busy_s"] == pytest.approx(750e-9)
    assert r["device_ops"][0] == ["m kOutput", pytest.approx(400e-9)]
    assert "early.9" not in r["ops_by_name"]       # before the window
    # device 0 idles 500-600 and 800-1100, both while the host waits
    assert r["idle_gaps"] == [["host in wait", pytest.approx(400e-9)]]
    with pytest.raises(ValueError):
        T.reduce({"devices": {}, "host": tables["host"]})
    with pytest.raises(ValueError):
        T.reduce({"devices": tables["devices"], "host": []})


def test_reduce_on_the_recorded_trace():
    with open(FIXTURE) as f:
        fx = json.load(f)
    r = T.reduce(fx["tables"])
    pinned = fx["pinned"]
    assert r["window_s"] == pytest.approx(pinned["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(pinned["busy_s"], rel=1e-9)
    assert r["device_ops"][0][0] == pinned["top_group"]
    assert r["device_ops"][0][1] == pytest.approx(pinned["top_group_s"],
                                                  rel=1e-9)
    assert r["top_ops"][0][0] == pinned["top_op"]
    idle = 1.0 - r["busy_s"] / r["window_s"]
    assert idle == pytest.approx(pinned["idle_share"], rel=1e-6)
    assert 0.0 <= idle < 0.2
