"""Reduction of a profiler trace to what the per-layer metrics read.

Two steps, kept apart so the second can be tested on a small recorded
trace without a chip:

1. :func:`load` reads the ``.xplane.pb`` the JAX profiler wrote and keeps a
   compact form: for every device plane its ``XLA Modules``, ``XLA Ops``
   and ``Async XLA Ops`` lines, and the host planes' events, each event as
   ``[name, start_ns, duration_ns]``.
2. :func:`reduce` takes that form and the traced window (the first and last
   of the benchmark's host markers at epoch boundaries) and gives, per
   device: the busy time (union of op intervals), the time and count of
   each XLA module, the self time of each op by module, the collectives'
   time and the part of it with no other op running, and the idle gaps
   named by what the host was doing.

On a TPU the ``XLA Ops`` line nests: a ``while`` op spans the ops of its
body. Busy time is a union, so nesting does not count twice; per-op times
are self times (an op's span less its children's), and "no other op
running" means no leaf op.
"""
from __future__ import annotations

import bisect
import glob
import re
from pathlib import Path

MODULES, OPS, ASYNC = "XLA Modules", "XLA Ops", "Async XLA Ops"
COLLECTIVE = re.compile(r"all-gather", re.I)


def _device_index(plane_name: str):
    m = re.match(r"^/device:([A-Za-z]+):(\d+)$", plane_name)
    if m and m.group(1).upper() != "CPU":
        return int(m.group(2))
    return None


def load(trace_dir) -> dict:
    """Compact form of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(trace_dir) / "plugins/profile/*/*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    planes = []
    for plane in data.planes:
        dev = _device_index(plane.name)
        lines, skipped = [], []
        for line in plane.lines:
            if dev is not None and line.name not in (MODULES, OPS, ASYNC):
                skipped.append(line.name)
                continue
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines or skipped:
            planes.append({"name": plane.name, "lines": lines, "skipped": skipped})
    return {"planes": planes}


def markers(tr: dict, name: str):
    """Start times of the host events called ``name``, in order."""
    out = [e[1] for p in tr["planes"] if _device_index(p["name"]) is None
           for line in p["lines"] for e in line["events"] if e[0] == name]
    return sorted(out)


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name.strip())


def _clip(events, lo, hi):
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e))
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _length(merged):
    return sum(e - s for s, e in merged)


def _minus(intervals, merged):
    """Length of ``intervals`` not covered by the disjoint sorted ``merged``."""
    starts = [s for s, _ in merged]
    total = 0
    for s, e in _union(intervals):
        covered = 0
        i = max(0, bisect.bisect_right(starts, s) - 1)
        while i < len(merged) and merged[i][0] < e:
            covered += max(0, min(e, merged[i][1]) - max(s, merged[i][0]))
            i += 1
        total += (e - s) - covered
    return total


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _op_name(name: str) -> str:
    """``fusion.12`` from an HLO line such as ``%fusion.12 = f32[...] ...``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _self_times(ops):
    """Per op (sorted by start): its span less its direct children's, and
    whether it has no child. Children lie inside their parent's span."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    self_ns = [e - s for _, s, e in ops]
    leaf = [True] * len(ops)
    stack = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            self_ns[stack[-1]] -= e - s
            leaf[stack[-1]] = False
        stack.append(i)
    return self_ns, leaf


def reduce(tr: dict, lo: int, hi: int, n_gaps: int = 10) -> dict:
    """Per device index: ``busy_ns``, ``modules`` {name: [ns, count]},
    ``ops`` {module/op: self ns}, ``collective_ns``,
    ``collective_exposed_ns`` and ``gaps`` (the ``n_gaps`` longest idle
    stretches as [host activity, ns]), all inside ``[lo, hi]``; and
    ``window_ns``."""
    host = [e for p in tr["planes"] if _device_index(p["name"]) is None
            for line in p["lines"] for e in line["events"]]
    host = _clip(host, lo, hi)
    out = {"window_ns": hi - lo, "devices": {}}
    for plane in tr["planes"]:
        dev = _device_index(plane["name"])
        if dev is None:
            continue
        mods = sorted(_clip(_line(plane, MODULES), lo, hi), key=lambda e: e[1])
        ops = [(_op_name(n), s, e) for n, s, e in _clip(_line(plane, OPS), lo, hi)]
        modules = {}
        for name, s, e in mods:
            acc = modules.setdefault(_module_name(name), [0, 0])
            acc[0] += e - s
            acc[1] += 1
        mstarts = [s for _, s, _ in mods]
        self_ns, leaf = _self_times(ops)
        by_op = {}
        for (name, s, e), ns in zip(ops, self_ns):
            i = bisect.bisect_right(mstarts, s) - 1
            mod = _module_name(mods[i][0]) if i >= 0 and mods[i][2] >= e else "?"
            key = f"{mod}/{name}"
            by_op[key] = by_op.get(key, 0) + ns
        busy = _union([(s, e) for _, s, e in ops] or [(s, e) for _, s, e in mods])
        asyncs = [(_op_name(n), s, e) for n, s, e in _clip(_line(plane, ASYNC), lo, hi)]
        coll = [(s, e) for name, s, e in ops + asyncs if COLLECTIVE.search(name)]
        other = _union([(s, e) for (name, s, e), is_leaf in zip(ops, leaf)
                        if is_leaf and not COLLECTIVE.search(name)])
        gaps, prev = [], lo
        for s, e in busy + [[hi, hi]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        out["devices"][dev] = {
            "busy_ns": _length(busy),
            "modules": modules,
            "ops": by_op,
            "collective_ns": _length(_union(coll)),
            "collective_exposed_ns": _minus(coll, other),
            "gaps": [[_host_activity(host, s, e), e - s] for s, e in gaps[:n_gaps]],
        }
    return out


def _host_activity(host, s, e) -> str:
    """Name of the host event that overlaps ``[s, e]`` most (the shorter
    one on a tie), or ``idle`` where none does."""
    best, key = "idle", None
    for name, hs, he in host:
        ov = min(e, he) - max(s, hs)
        if ov > 0:
            k = (ov, -(he - hs))
            if key is None or k > key:
                best, key = name, k
    return best


def breakdown(red: dict, device: int = 0, n: int = 10) -> dict:
    """The ``breakdown`` of the result line: the device ops that took most
    time and the longest idle gaps, in seconds."""
    d = red["devices"][device]
    ops = sorted(d["ops"].items(), key=lambda kv: -kv[1])[:n]
    return {
        "device_ops": [[name, ns / 1e9] for name, ns in ops],
        "idle_gaps": [[name, ns / 1e9] for name, ns in d["gaps"][:n]],
    }
