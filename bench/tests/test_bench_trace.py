"""The trace reduction, on a hand-made trace with known answers and on a
small trace recorded on a TPU v5e.

``data/trace_small.json.gz`` holds ``trace.load``'s compact form of 60 ms
of a traced cifar10 run, cut by hand, with three keys added: ``window``
(the span reduced), ``expect`` (the reduction's answers) and ``source``
(where it was recorded)."""
import gzip
import json

import benchutil
import pytest

from bench import jobs, trace

DATA = benchutil.ROOT / "bench/tests/data"


def _hand_trace():
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_segment(11)", 100, 420], ["jit_fwd(12)", 700, 100]]},
        {"name": "XLA Ops", "events": [
            ["%while.0 = (s32[]) while(...)", 340, 170],
            ["%fusion.1 = f32[8] fusion(...)", 100, 150],
            ["all-gather.2", 200, 100],
            ["fusion.3", 350, 150], ["copy.4", 700, 50]]},
    ]}
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        [jobs.MARKER, 50, 0], ["PjitFunction(fwd)", 520, 170],
        [jobs.MARKER, 1050, 0]]}]}
    return {"planes": [dev, host]}


def test_hand_made_trace():
    tr = _hand_trace()
    lo, hi = trace.markers(tr, jobs.MARKER)[0], trace.markers(tr, jobs.MARKER)[-1]
    assert (lo, hi) == (50, 1050)
    red = trace.reduce(tr, lo, hi)
    d = red["devices"][0]
    assert red["window_ns"] == 1000
    # ops cover [100, 300) and [340, 510) and [700, 750)
    assert d["busy_ns"] == 200 + 170 + 50
    assert d["modules"] == {"jit_segment": [420, 1], "jit_fwd": [100, 1]}
    assert d["ops"]["jit_segment/all-gather.2"] == 100
    # the while op spans fusion.3: its self time is its span less the child
    assert d["ops"]["jit_segment/while.0"] == 20
    assert d["ops"]["jit_fwd/copy.4"] == 50
    # the all-gather [200, 300) overlaps fusion.1 on [200, 250)
    assert d["collective_ns"] == 100 and d["collective_exposed_ns"] == 50
    # gaps: [750, 1050) 300, [510, 700) 190 (host dispatching), [50, 100)
    # 50 and [300, 340) 40
    assert d["gaps"] == [["idle", 300], ["PjitFunction(fwd)", 190],
                         ["idle", 50], ["idle", 40]]
    b = trace.breakdown(red)
    assert b["device_ops"][0] == ["jit_segment/fusion.1", 150e-9]
    assert b["idle_gaps"][1] == ["PjitFunction(fwd)", 190e-9]


def test_events_are_clipped_to_the_window():
    red = trace.reduce(_hand_trace(), 150, 320)
    d = red["devices"][0]
    assert d["busy_ns"] == 150 and d["modules"]["jit_segment"] == [170, 1]
    assert "jit_fwd" not in d["modules"]


def test_recorded_trace():
    path = DATA / "trace_small.json.gz"
    if not path.exists():
        pytest.fail(f"missing {path}")
    tr = json.loads(gzip.decompress(path.read_bytes()))
    expect = tr["expect"]
    red = trace.reduce(tr, *tr["window"])
    d = red["devices"][0]
    assert red["window_ns"] == expect["window_ns"]
    assert d["busy_ns"] == expect["busy_ns"]
    assert {k: v[1] for k, v in d["modules"].items()} == expect["module_counts"]
    assert max(d["ops"].items(), key=lambda kv: kv[1]) == tuple(expect["top_op"])
    assert 0 < d["busy_ns"] <= red["window_ns"]
    # nested ops: self times add up to no more than the busy time
    assert sum(d["ops"].values()) <= d["busy_ns"]
    gaps = trace.breakdown(red)["idle_gaps"]
    assert gaps and all(name != "idle" for name, _ in gaps[:3])
