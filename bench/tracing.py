"""Profiler capture of part of a run's window, and its reduction to numbers.

The capture is a ``jax.profiler`` trace whose traced span is marked on the
host by a ``bench/traced`` annotation. The reduction reads the ``.xplane.pb``
with ``jax.profiler.ProfileData`` and works on the device planes' ``XLA Ops``
lines (one event per executed HLO op) and ``XLA Modules`` lines (one event per
program execution):

- busy: the union of op intervals inside the traced span, per device;
- path time: op time by the op's named-scope path (its ``op_name``, from
  the op's own stats or from the compiled program's op names, with
  transformation wrappers such as ``jit(...)`` and ``vmap(...)`` taken
  off), over the executions below; :func:`scope_seconds` sums it for one
  ``jax.named_scope`` label, wherever it sits in the path;
- executions: program executions wholly inside the span, of the program
  that took most device time;
- idle gaps: the spans between busy intervals, each labelled with the host
  annotations (``bench/...`` from the benchmark, ``serve/...`` from the
  server's telemetry) open at the gap's middle.

``python bench/tracing.py <trace dir>`` prints what a trace holds, for a
first look by hand.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import sys
import time
from collections import defaultdict
from typing import Dict, List, Tuple

SPAN_MARK = "bench/traced"
HOST_PREFIXES = ("bench/", "serve/")


class Capture:
    """Starts and stops one ``jax.profiler`` trace into ``trace_dir``; the
    traced span is annotated on the calling thread. A disabled capture does
    nothing."""

    def __init__(self, enabled: bool, trace_dir: str, max_s: float):
        self.enabled, self.trace_dir, self.max_s = enabled, trace_dir, max_s
        self.active = False
        self.t0 = self.t1 = None
        self._mark = None

    def start(self):
        if not self.enabled or self.t0 is not None:
            return
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self._mark = jax.profiler.TraceAnnotation(SPAN_MARK)
        self._mark.__enter__()
        self.t0 = time.perf_counter()
        self.active = True

    def tick(self, now: float):
        """Stop once ``max_s`` has passed since the start."""
        if self.active and now - self.t0 >= self.max_s:
            self.stop()

    def stop(self):
        if not self.active:
            return
        import jax
        self.t1 = time.perf_counter()
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except Exception:               # events without readable stats
        return {}


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def _ops_line(lines: dict):
    """The line of executed ops: ``XLA Ops``, else the busiest line."""
    if "XLA Ops" in lines:
        return lines["XLA Ops"]
    named = [ln for n, ln in lines.items() if "Op" in n and "Module" not in n]
    return max(named, key=lambda ln: len(list(ln.events)), default=None)


_HLO_INSTR = re.compile(r'^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$')
_HLO_COMP = re.compile(r'^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$')
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r'calls=%?([\w.\-]+)')


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its ``op_name`` metadata (the named-scope
    path), from a compiled program's text: the scope of trace events that
    carry no such stat. A fusion without metadata of its own takes the
    first ``op_name`` inside the computation it calls, nested fusions
    included."""
    own, calls, first = {}, {}, {}
    inner: Dict[str, List[str]] = defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        c = _HLO_COMP.match(line)
        if c:
            comp = c.group(1)
            continue
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(m.group(2))
        k = _CALLS.search(m.group(2))
        if op:
            own.setdefault(m.group(1), op.group(1))
            first.setdefault(comp, op.group(1))
        elif k:
            calls[m.group(1)] = k.group(1)
        if k:
            inner[comp].append(k.group(1))

    def first_of(name, seen=()):
        if name in first or name in seen:
            return first.get(name)
        for callee in inner.get(name, ()):
            found = first_of(callee, seen + (name,))
            if found:
                first[name] = found
                return found
        return None

    for name, callee in calls.items():
        found = first_of(callee)
        if found:
            own.setdefault(name, found)
    return own


_INSTR = re.compile(r"^%?([\w.\-]+) = ")
_OPCODE = re.compile(r" = .*?\s([a-z][\w\-]*)\(")
CONTAINERS = ("while", "conditional", "call")   # ops that hold other ops


def _instruction(name: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an op event, whose name on a TPU is
    the HLO instruction's text: ``%fusion.3 = f32[8]{0} fusion(...)``."""
    m = _INSTR.match(name)
    if not m:
        return name, ""
    op = _OPCODE.search(name)
    return m.group(1), op.group(1) if op else ""


_WRAPPER = re.compile(r"[\w.\-]*\(")


def scope_path(op_name: str) -> str:
    """An ``op_name`` with transformation wrappers taken off:
    ``jit(infer)/vmap(graphx/knn_edges)/jit(take)/gather`` ->
    ``infer/graphx/knn_edges/take/gather``."""
    flat = _WRAPPER.sub("", op_name).replace(")", "")
    return "/".join(seg for seg in flat.split("/") if seg)


def scope_seconds(reduced: dict, scope: str):
    """Device time of the ops whose path holds ``scope`` (one or more whole
    segments, such as ``graphx/knn_edges``), or None where no op does."""
    want = "/" + scope.strip("/") + "/"
    hits = [t for path, t in reduced["path_s"].items()
            if want in "/" + path + "/"]
    return sum(hits) if hits else None


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _op_name(instr: str, stats: dict, op_names: Dict[str, str]) -> str:
    """An op's ``op_name``: from the compiled program's map, else the first
    stat of the event that reads as a scope path."""
    if instr in op_names:
        return op_names[instr]
    return next((v for v in stats.values()
                 if isinstance(v, str) and "/" in v), "")


STRUCTURE = {"while", "body", "cond", "closed_call", "checkpoint", "remat",
             "branch"}          # path segments of control flow, not scopes


def _label(path: str) -> str:
    """What an op is listed under in the breakdown: its path without the
    program's name, the primitive and control-flow segments."""
    segs = [x for x in path.split("/")[1:-1] if x not in STRUCTURE]
    return "/".join(segs) if segs else "other"


def reduce_trace(pd, op_names: Dict[str, str] = None) -> dict:
    """Numbers from a ``ProfileData``: see the module docstring.
    ``op_names`` (see :func:`hlo_op_names`) gives each op's named-scope path
    where the trace's own stats lack it.

    Returns ``{window_s, busy_s, path_s: {scope path: s}, executions,
    device_ops: [[name, s]...], idle_gaps: [[label, s]...], devices}``,
    with ``busy_s``, ``path_s`` and ``executions`` averaged over devices.
    An op is named ``<label>:<instruction>`` in ``device_ops`` (see
    :func:`_label`).
    """
    op_names = op_names or {}
    span = None
    host: List[Tuple[int, int, str]] = []
    devices = []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == SPAN_MARK:
                    span = (int(ev.start_ns),
                            int(ev.start_ns + ev.duration_ns))
                elif name.startswith(HOST_PREFIXES):
                    host.append((int(ev.start_ns),
                                 int(ev.start_ns + ev.duration_ns), name))
    if span is None:
        raise ValueError(f"no {SPAN_MARK} annotation in the trace")
    lo, hi = span
    busy_total, path_total, exec_total = 0, defaultdict(int), 0
    op_time: Dict[str, int] = defaultdict(int)
    gaps: List[Tuple[int, int]] = []
    n_dev = 0
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        ops_line = _ops_line(lines)
        if ops_line is None:
            continue
        n_dev += 1
        mods = []
        if "XLA Modules" in lines:
            # a module event may carry a run id after its name: "jit_f(12)"
            mods = [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                     re.sub(r"\(\d+\)$", "", e.name))
                    for e in lines["XLA Modules"].events]
        per_mod: Dict[str, int] = defaultdict(int)
        for a, b, name in mods:
            per_mod[name] += max(0, min(b, hi) - max(a, lo))
        main = max(per_mod, key=per_mod.get) if per_mod else None
        whole = sorted((a, b) for a, b, name in mods
                       if name == main and a >= lo and b <= hi)
        starts = [a for a, _ in whole]
        exec_total += len(whole)
        intervals = []
        for ev in ops_line.events:
            a = int(ev.start_ns)
            b = a + int(ev.duration_ns)
            if b <= lo or a >= hi:
                continue
            a, b = max(a, lo), min(b, hi)
            intervals.append((a, b))
            instr, opcode = _instruction(ev.name)
            if opcode in CONTAINERS:        # its body's ops count instead
                continue
            path = scope_path(_op_name(instr, _stats(ev), op_names))
            op_time[f"{_label(path)}:{instr}"] += b - a
            k = bisect.bisect_right(starts, a) - 1
            if path and k >= 0 and b <= whole[k][1]:
                path_total[path] += b - a
        merged = _union(intervals)
        busy_total += sum(b - a for a, b in merged)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if n_dev == 0:
        raise ValueError("no device plane with a line of executed ops")

    def label(a, b):
        mid = (a + b) // 2
        open_ = sorted({n for s, e, n in host if s <= mid <= e})
        return "+".join(open_) if open_ else "no host annotation"

    gap_time: Dict[str, int] = defaultdict(int)
    for a, b in gaps:
        gap_time[label(a, b)] += b - a
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gap_time.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": busy_total / n_dev / 1e9,
            "path_s": {p: t / n_dev / 1e9 for p, t in path_total.items()},
            "executions": exec_total / n_dev,
            "device_ops": [[k, v / n_dev / 1e9] for k, v in top_ops],
            "idle_gaps": [[k, v / n_dev / 1e9] for k, v in top_gaps],
            "devices": n_dev}


def load(trace_dir: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(find_xplane(trace_dir))


class _Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def export_json(pd, path: str) -> None:
    """Write the parts of a trace the reduction reads, inside the traced
    span, as gzipped JSON (test data)."""
    import gzip
    import json
    span = None
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == SPAN_MARK:
                    span = (ev.start_ns, ev.start_ns + ev.duration_ns)
    planes = []
    for plane in pd.planes:
        keep_all = _is_device_plane(plane.name)
        lines = []
        for line in plane.lines:
            evs = []
            for ev in line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                if b < span[0] or a > span[1]:
                    continue
                if not keep_all and not (ev.name == SPAN_MARK or
                                         ev.name.startswith(HOST_PREFIXES)):
                    continue
                st = {k: v for k, v in _stats(ev).items()
                      if isinstance(v, str) and "/" in v}
                evs.append([ev.name, a, ev.duration_ns, st])
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    with gzip.open(path, "wt") as f:
        json.dump({"planes": planes}, f)


def load_json(path: str):
    """A trace written by :func:`export_json`, readable by
    :func:`reduce_trace`."""
    import gzip
    import json
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return _Obj(planes=[_Obj(name=p["name"], lines=[
        _Obj(name=ln["name"], events=[
            _Obj(name=n, start_ns=a, duration_ns=d, stats=list(st.items()))
            for n, a, d, st in ln["events"]]) for ln in p["lines"]])
        for p in raw["planes"]])


def describe(trace_dir: str, per_line: int = 3) -> None:
    """Print the planes and lines of a trace with a few events each."""
    pd = load(trace_dir)
    for plane in pd.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs), "events")
            for ev in evs[:per_line]:
                print("    ", repr(ev.name), int(ev.start_ns),
                      int(ev.duration_ns), _stats(ev))


if __name__ == "__main__":
    describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 3)
