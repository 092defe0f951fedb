"""The program's names in the profiler trace (``bench/program_trace.py``):
the pass scopes and host spans of a small trace recorded on a TPU v5e, and
the window check on a trace made here on the CPU.

``data/program_trace_small.json.gz`` holds ``program_trace.load``'s
compact form of a traced cifar10 run, cut by hand, and the part of
``trace.reduce``'s output that the readers take (``red``): the step
module's time and its ops' self times on device 0. ``source`` says where
it was recorded and ``expect`` holds the readings that run printed."""
import gzip
import json

import benchutil
import pytest

from bench import harness, jobs, program_trace

DATA = benchutil.ROOT / "bench/tests/data"
STEP = "jit_segment"


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "program_trace_small.json.gz"
    if not path.exists():
        pytest.fail(f"missing {path}")
    pt = json.loads(gzip.decompress(path.read_bytes()))
    pt["red"]["devices"] = {int(k): v for k, v in pt["red"]["devices"].items()}
    return pt


class Ctx:
    def __init__(self, red, traced_epochs=2, steps=390):
        self.red = red
        self.traffic = {"programs": {"step": STEP}}
        self.chips = 1
        self.traced_epochs = traced_epochs
        self.worker_steps_per_chip = steps
        self.notes = []

    def note(self, msg):
        self.notes.append(msg)


@pytest.mark.parametrize("path, scope", [
    ("jit(segment)/while/body/closed_call/jvp(espmm_fwd)/mul", "espmm_fwd"),
    ("jit(segment)/while/body/closed_call/transpose(jvp(espmm_dx))/add", "espmm_dx"),
    # a transposed op under the forward's name: the innermost pass counts
    ("jit(segment)/jvp(espmm_fwd)/transpose(jvp(espmm_dw))/reduce_sum", "espmm_dw"),
    ("jit(segment)/while/body/my_espmm_fwd2/mul", None),
    ("jit(segment)/while/body/espmm_fwd_extra/mul", None),
    ("jit(segment)/while/body/closed_call/jvp(all_relu)/max", None),
    ("", None),
])
def test_scope_is_the_last_whole_pass_name(path, scope):
    assert program_trace.scope_of(path) == scope


def test_recorded_pass_sums_stay_within_the_step_module(recorded):
    claimed, module_ns, unscoped = program_trace.pass_ns(
        recorded, recorded["red"], STEP, 1)
    assert all(claimed[s] > 0 for s in program_trace.SCOPES)
    assert sum(claimed.values()) <= module_ns
    ops = {k: v for k, v in recorded["red"]["devices"][0]["ops"].items()
           if k.startswith(STEP + "/")}
    assert sum(claimed.values()) + sum(ns for _, ns in unscoped) == pytest.approx(
        sum(ops.values()))


@pytest.mark.parametrize("scope", program_trace.SCOPES)
def test_recorded_pass_readings_are_the_chip_runs(recorded, monkeypatch, scope):
    """The pure part gives, from the excerpt, what the chip run printed."""
    monkeypatch.setattr(program_trace, "for_run", lambda ctx: recorded)
    ctx = Ctx(recorded["red"], recorded["traced_epochs"], recorded["steps_per_epoch"])
    assert program_trace.pass_ms(ctx, scope) == pytest.approx(
        recorded["expect"][f"{scope}_ms.train"], rel=1e-12)


def test_recorded_unscoped_ops_are_reported(recorded, monkeypatch):
    _, _, unscoped = program_trace.pass_ns(recorded, recorded["red"], STEP, 1)
    assert unscoped and all(recorded["scopes"].get(op) is None for op, _ in unscoped)
    assert [ns for _, ns in unscoped] == sorted((ns for _, ns in unscoped), reverse=True)
    monkeypatch.setattr(program_trace, "for_run", lambda ctx: recorded)
    ctx = Ctx(recorded["red"])
    assert program_trace.pass_ms(ctx, "espmm_dw") is not None
    assert len(ctx.notes) == 1
    assert all(op in ctx.notes[0] for op, _ in unscoped[:3])


def test_no_scopes_read_nothing(recorded, monkeypatch):
    """A program without the pass scopes (an older tree) gives no pass
    reading, and raises nothing."""
    plain = dict(recorded, scopes={op: None for op in recorded["scopes"]})
    monkeypatch.setattr(program_trace, "for_run", lambda ctx: plain)
    for scope in program_trace.SCOPES:
        assert program_trace.pass_ms(Ctx(recorded["red"]), scope) is None


def test_recorded_eval_span_is_clipped_at_the_window_edge(recorded, monkeypatch):
    lo, hi = recorded["markers"][0], recorded["markers"][-1]
    evals = sorted(s for s in recorded["host"]["train.eval"] if lo <= s[0] and s[1] <= hi)
    total, idle = program_trace.span_ns(recorded, "train.eval", lo, hi)
    assert total == sum(e - s for s, e in evals)
    assert 0 <= idle < total
    monkeypatch.setattr(program_trace, "for_run", lambda ctx: recorded)
    ctx = Ctx(recorded["red"], recorded["traced_epochs"])
    assert program_trace.eval_ms_per_epoch(ctx) == pytest.approx(
        recorded["expect"]["eval_ms_per_epoch.train"], rel=1e-12)
    assert len(ctx.notes) == 1 and "idle" in ctx.notes[0]
    # a window edge inside the first eval span cuts it there
    s0, e0 = evals[0]
    mid = (s0 + e0) // 2
    cut, _ = program_trace.span_ns(recorded, "train.eval", mid, hi)
    assert cut == total - (mid - s0)


def _pb(num, value):
    """One protobuf field: a varint for an int, length-delimited otherwise."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(num << 3 | 2) + varint(len(value)) + value


def test_op_stats_reads_the_event_metadata(tmp_path):
    """The op path is read from ``XPlane.event_metadata``, as a string stat
    or as a reference to an interned string, on device planes only."""
    fwd = "jit(segment)/while/body/closed_call/jvp(espmm_fwd)/mul"
    dx = "jit(segment)/while/body/closed_call/transpose(jvp(espmm_dx))/add"

    def meta(mid, name, display, stat):
        body = _pb(1, mid) + _pb(2, name) + _pb(4, display) + _pb(5, stat)
        return _pb(4, _pb(1, mid) + _pb(2, body))

    def stat_meta(sid, name):
        return _pb(5, _pb(1, sid) + _pb(2, _pb(1, sid) + _pb(2, name)))

    device = (_pb(1, 3) + _pb(2, "/device:TPU:0")
              + meta(7, "%fusion.3 = f32[8] fusion(...)", "fusion.3",
                     _pb(1, 1) + _pb(5, fwd))
              + meta(8, "%copy.4 = f32[8] copy(...)", "", _pb(1, 1) + _pb(7, 2))
              + meta(9, "%add.5 = f32[8] add(...)", "", _pb(1, 3) + _pb(5, fwd))
              + stat_meta(1, program_trace.SCOPE_STAT) + stat_meta(2, dx)
              + stat_meta(3, "long_name"))
    host = _pb(2, "/host:CPU") + meta(7, "train.eval", "", _pb(1, 1) + _pb(5, fwd))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb(1, device) + _pb(1, host) + _pb(4, "host"))
    assert program_trace.op_stats(path) == {"/device:TPU:0": {
        "%fusion.3 = f32[8] fusion(...)": fwd, "fusion.3": fwd,
        "%copy.4 = f32[8] copy(...)": dx}}


def _cpu_trace(tmp_path, epochs=2):
    """A profiler trace written here: ``epochs`` epochs between the
    benchmark's markers, each with a ``train.eval`` span."""
    import jax
    import jax.numpy as jnp

    from jax.profiler import TraceAnnotation

    root = tmp_path / "trace"
    (root / "cell").mkdir(parents=True)
    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(8)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(root / "cell"))
    with TraceAnnotation(jobs.MARKER):
        pass
    for _ in range(epochs):
        with TraceAnnotation("train.eval"):
            f(x).block_until_ready()
        with TraceAnnotation(jobs.MARKER):
            pass
    jax.profiler.stop_trace()
    return root


@pytest.mark.parametrize("off_by", [0, 1])
def test_reader_takes_only_the_reduced_window(tmp_path, off_by):
    """The newest ``.xplane.pb`` is read only where its marker window is the
    one ``trace.reduce`` reduced; a window off by a nanosecond reads None."""
    root = _cpu_trace(tmp_path)
    path = program_trace.newest(root)
    marks = program_trace.load(path, STEP)["markers"]
    red = {"window_ns": marks[-1] - marks[0] + off_by, "devices": {}}
    pt = program_trace.for_run(Ctx(red), root)
    if off_by:
        assert pt is None
        return
    total, idle = program_trace.span_ns(pt, "train.eval", marks[0], marks[-1])
    assert len(pt["host"]["train.eval"]) == 2 and 0 < idle <= total
    assert program_trace.pass_ns(pt, red, STEP, 1) is None


def test_readers_are_found_by_name():
    spec = harness.load_spec()
    for cell in ("cifar10.seq-train", "leukemia.seq-train"):
        readers = harness.resolve(spec, cell)["readers"]
        for name in ("espmm_fwd_ms.train", "espmm_dx_ms.train",
                     "espmm_dw_ms.train", "eval_ms_per_epoch.train"):
            assert callable(harness.load_reader(readers[name]))
