"""The program's own names in the traced run's profiler trace: the espmm
pass scope of each step-module op, and the host spans of the epoch loop.

``trace.reduce`` keeps each device op's self time keyed ``module/op``, but
neither the ops' metadata nor the host spans by name. The readers of the
espmm pass times and of evaluation time therefore re-open the traced run's
``.xplane.pb`` (the harness deletes it only after every reader has run)
and keep, once per file:

* ``scopes``: step-module op name -> the innermost pass scope on that op's
  path. On a TPU v5e the metadata of an ``XLA Ops`` event carries the op's
  ``op_name`` in the ``tf_op`` stat, e.g. ``jit(segment)/while/body/
  closed_call/transpose(jvp(espmm_dx))/while/body/closed_call/
  scatter-add:`` (there is no ``long_name`` stat); the event name is the
  HLO text. The pass is the last of ``espmm_fwd``, ``espmm_dx`` and
  ``espmm_dw`` on the path, matched as a whole name: transposed ops may
  carry the forward's name higher up. JAX's persistent compilation cache
  keys ignore op metadata, so a program without the scopes that loads an
  executable cached with them reads them too;
* ``host``: host events by name as ``[start_ns, end_ns]``;
* ``markers``: the benchmark's epoch-boundary markers;
* ``busy``: device 0's op intervals, merged.

The file is used only where its marker window is the one ``trace.reduce``
reduced (``red["window_ns"]``); otherwise every reading is ``None``, never
a number from another run. Everything below :func:`load` is pure and runs
on the compact form, which ``tests/data/program_trace_small.json.gz``
holds for a recorded v5e run.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from pathlib import Path

from bench import jobs, trace

TRACES = Path(__file__).resolve().parent / ".out" / "trace"
SCOPES = ("espmm_fwd", "espmm_dx", "espmm_dw")
SCOPE_STAT = "tf_op"
_SCOPE = re.compile(r"\b(espmm_(?:fwd|dx|dw))\b")
_cache: dict = {}


def scope_of(op_path: str):
    """The last pass scope named on ``op_path``, or ``None``."""
    found = _SCOPE.findall(op_path or "")
    return found[-1] if found else None


def newest(root: Path = TRACES):
    files = glob.glob(str(root / "*" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: integers for
    varints, slices of ``buf`` otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            size = {1: 8, 5: 4}.get(kind)
            if size is None:
                size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode(errors="replace")


def op_stats(path, stat: str = SCOPE_STAT) -> dict:
    """{device plane name: {event metadata name or display name: the
    string stat ``stat``}} of the ``.xplane.pb`` at ``path``. The metadata
    of an event is in the file's ``XPlane.event_metadata``, which
    ``ProfileData`` does not expose (XSpace.planes 1; XPlane.name 2,
    event_metadata 4 as map entries {key 1, value 2}, stat_metadata 5;
    XEventMetadata.name 2, display_name 4, stats 5; XStat.metadata_id 1,
    str_value 5, ref_value 7; XStatMetadata.id 1, name 2). The fields are
    decoded here because the one installed ``xplane_pb2`` is TensorFlow's,
    and importing it would load TensorFlow into the process on the chip."""
    buf = memoryview(Path(path).read_bytes())
    out = {}
    for num, plane in _fields(buf):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = _text(v)
                if trace._device_index(name) is None:
                    break
            elif f in (4, 5):
                entry = dict(_fields(v)).get(2)
                if entry is None:
                    continue
                if f == 4:
                    metas.append(entry)
                else:
                    meta = dict(_fields(entry))
                    stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        else:
            ids = {i for i, n in stat_names.items() if n == stat}
            found = {}
            for entry in metas:
                names, value = [], None
                for g, v in _fields(entry):
                    if g in (2, 4):
                        names.append(_text(v))
                    elif g == 5:
                        st = dict(_fields(v))
                        if st.get(1) in ids:
                            value = (_text(st[5]) if 5 in st
                                     else stat_names.get(st.get(7), ""))
                if value is not None:
                    found.update((n, value) for n in names if n)
            out[name] = found
    return out


def load(path, step: str) -> dict:
    """Compact form of the ``.xplane.pb`` at ``path`` (module docstring),
    with the scopes of XLA module ``step``'s ops."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    paths = op_stats(path)
    scopes, host, busy = {}, {}, []
    for plane in data.planes:
        dev = trace._device_index(plane.name)
        if dev is None:
            for line in plane.lines:
                for e in line.events:
                    s = int(e.start_ns)
                    host.setdefault(e.name, []).append([s, s + int(e.duration_ns)])
            continue
        lines = {line.name: line for line in plane.lines}
        if trace.OPS not in lines or trace.MODULES not in lines:
            continue
        mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                       trace._module_name(e.name))
                      for e in lines[trace.MODULES].events)
        mstarts = [m[0] for m in mods]
        for e in lines[trace.OPS].events:
            s = int(e.start_ns)
            end = s + int(e.duration_ns)
            if dev == 0:
                busy.append((s, end))
            i = bisect.bisect_right(mstarts, s) - 1
            if i < 0 or mods[i][2] != step or mods[i][1] < end:
                continue
            op = trace._op_name(e.name)
            if op not in scopes:
                scopes[op] = scope_of(paths.get(plane.name, {}).get(e.name))
    return {"scopes": scopes, "host": host,
            "markers": sorted(s for s, _ in host.get(jobs.MARKER, [])),
            "busy": trace._union(busy)}


def for_run(ctx, root: Path = TRACES):
    """The compact form of the newest trace under ``root``, built once per
    file, or ``None`` where its marker window is not ``ctx.red``'s."""
    path = newest(root)
    if ctx.red is None or path is None:
        return None
    step = ctx.traffic["programs"]["step"]
    key = (path, os.path.getmtime(path), step)
    if key not in _cache:
        _cache.clear()
        _cache[key] = load(path, step)
    pt = _cache[key]
    return pt if in_window(pt, ctx.red) else None


def in_window(pt, red) -> bool:
    m = pt["markers"]
    return len(m) >= 2 and m[-1] - m[0] == red["window_ns"]


def pass_ns(pt, red, step: str, chips: int):
    """Device time of the step module's ops under each pass scope, averaged
    over ``chips``: ``({scope: ns}, step module ns, [(op, ns), ...] of the
    ops no scope claims, longest first)``."""
    devs = [red["devices"].get(i) for i in range(chips)]
    if any(d is None or step not in d["modules"] for d in devs):
        return None
    claimed = {s: 0.0 for s in SCOPES}
    unscoped = {}
    prefix = f"{step}/"
    for d in devs:
        for key, ns in d["ops"].items():
            if not key.startswith(prefix):
                continue
            op = key[len(prefix):]
            scope = pt["scopes"].get(op)
            if scope is None:
                unscoped[op] = unscoped.get(op, 0.0) + ns / chips
            else:
                claimed[scope] += ns / chips
    module_ns = sum(d["modules"][step][0] for d in devs) / chips
    return claimed, module_ns, sorted(unscoped.items(), key=lambda kv: -kv[1])


def span_ns(pt, name: str, lo: int, hi: int):
    """Host time of the spans called ``name`` clipped to ``[lo, hi]``, and
    the part of it in which device 0 ran no op."""
    spans = [(s, e) for _, s, e in
             trace._clip([(name, s, e - s) for s, e in pt["host"].get(name, [])],
                         lo, hi)]
    total = trace._length(trace._union(spans))
    return total, trace._minus(spans, pt["busy"])


def pass_ms(ctx, scope: str):
    """The reading of ``espmm_<pass>_ms.train``: device ms per step of the
    step module's ops under ``scope``, as ``step_ms.train`` divides."""
    pt = for_run(ctx)
    if pt is None or not ctx.traced_epochs:
        return None
    step = ctx.traffic["programs"]["step"]
    got = pass_ns(pt, ctx.red, step, ctx.chips)
    if got is None or not got[0][scope]:
        return None
    claimed, module_ns, unscoped = got
    share = 100.0 * (1.0 - sum(claimed.values()) / module_ns)
    top = ", ".join(f"{op} {ns / 1e6:.3f} ms" for op, ns in unscoped[:3])
    ctx.note(f"{scope}_ms.train: {share:.3f}% of {step}'s device time in no "
             f"pass scope; its longest ops: {top}")
    return claimed[scope] / 1e6 / (ctx.traced_epochs * ctx.worker_steps_per_chip)


def eval_ms_per_epoch(ctx, name: str = "train.eval"):
    """Host ms of the evaluation spans in the window per traced epoch."""
    pt = for_run(ctx)
    if pt is None or not ctx.traced_epochs:
        return None
    total, idle = span_ns(pt, name, pt["markers"][0], pt["markers"][-1])
    if not total:
        return None
    ctx.note(f"eval_ms_per_epoch.train: device 0 idle {idle / 1e6:.3f} ms "
             f"({100.0 * idle / total:.2f}%) of {total / 1e6:.3f} ms in {name}")
    return total / 1e6 / ctx.traced_epochs
