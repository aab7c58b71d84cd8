"""Capture of a profiler trace and its reduction to per-layer numbers.

``capture`` runs a block under ``jax.profiler`` (no Python tracer) and
``extract`` reads the ``.xplane.pb`` it wrote with ``ProfileData``:

- device operations: on a TPU the events of each ``/device:*`` plane's
  ``XLA Ops`` line, and the program executions of its ``XLA Modules``
  line; on the CPU, whose backend runs operations on host threads, the
  host events that carry an ``hlo_module`` statistic (one module
  execution per ``run_id``);
- host spans: the ``jax.profiler.TraceAnnotation`` events the harness
  writes around each call into the service (names start ``bench.``).

All events are on the trace's own clock, in nanoseconds.  ``Reduced``
then gives the union of busy device time, the idle share of the traced
window, device time per module and per span, program launches, and the
idle gaps labelled by the host span open at the time.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
from dataclasses import dataclass, field

SPAN_PREFIX = "bench."


@dataclass
class Op:
    start: int
    end: int
    name: str
    module: str
    category: str
    device: str
    span: str = ""

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclass
class Span:
    start: int
    end: int
    name: str


@dataclass
class Trace:
    ops: list = field(default_factory=list)        # [Op]
    modules: list = field(default_factory=list)    # [Op] (program runs)
    spans: list = field(default_factory=list)      # [Span]


@contextlib.contextmanager
def capture(out: dict):
    """Profile the block; ``out['path']`` is then the ``.xplane.pb``
    written, inside a temporary directory that ``cleanup(out)`` removes."""
    import jax

    d = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                                 recursive=True))
        out["dir"] = d
        out["path"] = found[-1] if found else None


def cleanup(out: dict) -> None:
    if out.get("dir"):
        shutil.rmtree(out["dir"], ignore_errors=True)


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:
        return {}


def _category(stats: dict) -> str:
    for key in ("hlo_category", "category"):
        if key in stats:
            return str(stats[key])
    return ""


def extract(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    cpu_runs: dict = {}
    for plane in pd.planes:
        pname = plane.name
        if pname.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        s = int(ev.start_ns)
                        tr.modules.append(Op(s, s + int(ev.duration_ns),
                                             ev.name, ev.name, "", pname))
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        s = int(ev.start_ns)
                        st = _stats(ev)
                        tr.ops.append(Op(s, s + int(ev.duration_ns),
                                         ev.name, "", _category(st), pname))
        elif pname.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if name.startswith(SPAN_PREFIX):
                        tr.spans.append(Span(s, e, name))
                        continue
                    st = _stats(ev)
                    if "hlo_module" in st and not name.startswith("end: "):
                        mod = str(st["hlo_module"])
                        tr.ops.append(Op(s, e, str(st.get("hlo_op", name)),
                                         mod, _category(st), "cpu"))
                        key = (mod, st.get("run_id"))
                        lo, hi = cpu_runs.get(key, (s, e))
                        cpu_runs[key] = (min(lo, s), max(hi, e))
    for (mod, _), (s, e) in cpu_runs.items():
        tr.modules.append(Op(s, e, mod, mod, "", "cpu"))
    attribute(tr)
    return tr


def attribute(tr: Trace) -> None:
    """Give each op its module (the program run that encloses it on its
    device) where the trace did not, and the innermost host span open
    when it started (spans are nested or disjoint)."""
    import bisect

    by_dev: dict = {}
    for m in tr.modules:
        by_dev.setdefault(m.device, []).append(m)
    for lst in by_dev.values():
        lst.sort(key=lambda m: m.start)
    starts = {d: [m.start for m in lst] for d, lst in by_dev.items()}
    for op in tr.ops:
        if op.module:
            continue
        lst = by_dev.get(op.device, [])
        i = bisect.bisect_right(starts.get(op.device, []), op.start) - 1
        if i >= 0 and lst[i].start <= op.start < max(lst[i].end,
                                                        lst[i].start + 1):
            op.module = lst[i].name
    spans = sorted(tr.spans, key=lambda s: (s.start, -s.end))
    sstarts = [s.start for s in spans]
    for op in list(tr.ops) + list(tr.modules):
        op.span = span_at(spans, sstarts, op.start)


def span_at(spans, starts, t) -> str:
    """Name of the innermost span containing t ('' for none)."""
    import bisect

    i = bisect.bisect_right(starts, t) - 1
    best = ""
    # Walk back over the spans that started before t: a nested span
    # starts after its parent, so the first that contains t is the
    # innermost.  The harness nests at most two levels with a few
    # children each, so a parent lies within a few steps.
    for j in range(i, max(i - 8, -1), -1):
        s = spans[j]
        if s.start <= t < s.end:
            best = s.name
            break
    return best


def short_name(name: str) -> str:
    """'%fusion.7 f32[8,2048,2048] kOutput' for a TPU op's HLO text."""
    if " = " not in name:
        return name[:100]
    lhs, rhs = name.split(" = ", 1)
    kind = re.search(r"kind=(k\w+)", rhs)
    shape = rhs.split(" ", 1)[0].split("{", 1)[0]
    return " ".join([lhs, shape] + ([kind.group(1)] if kind else []))


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of [s, e) intervals clipped to [lo, hi)."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: int, hi: int):
    """Idle [s, e) gaps of the union of ``intervals`` inside [lo, hi)."""
    out, t = [], lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class Reduced:
    """The numbers a traced window gives, for the per-layer readers."""

    def __init__(self, tr: Trace, lo: int, hi: int):
        self.trace = tr
        self.lo, self.hi = lo, hi
        self.window_ns = max(hi - lo, 1)
        devs = sorted({op.device for op in tr.ops})
        self.devices = devs
        per_dev = [union_ns([(o.start, o.end) for o in tr.ops
                             if o.device == d], lo, hi) for d in devs]
        self.busy_ns = (sum(per_dev) / len(per_dev)) if per_dev else 0.0
        self.ops = [o for o in tr.ops if lo <= o.start < hi]
        self.modules = [m for m in tr.modules if lo <= m.start < hi]
        self.spans = [s for s in tr.spans if lo <= s.start < hi]

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def idle_pct(self) -> float | None:
        if not self.ops:
            return None
        return 100.0 * (1.0 - self.busy_ns / self.window_ns)

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def op_seconds(self, pred) -> float:
        return sum(o.dur for o in self.ops if pred(o)) / 1e9

    def top_ops(self, n: int = 10):
        """[[name, seconds]]: device time by (module, op), largest first."""
        acc: dict = {}
        for o in self.ops:
            key = short_name(o.name)
            key = f"{o.module}/{key}" if o.module else key
            acc[key] = acc.get(key, 0) + o.dur
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def idle_gaps(self, n: int = 10):
        """[[label, seconds]]: the longest device-idle gaps, each labelled
        by the host span open at its middle ('none' where none was)."""
        if not self.devices:
            return []
        dev = self.devices[0]
        iv = [(o.start, o.end) for o in self.trace.ops if o.device == dev]
        spans = sorted(self.trace.spans, key=lambda s: (s.start, -s.end))
        starts = [s.start for s in spans]
        out = []
        for s, e in sorted(gaps(iv, self.lo, self.hi),
                           key=lambda g: g[0] - g[1])[:n]:
            label = span_at(spans, starts, (s + e) // 2) or "none"
            out.append([label, (e - s) / 1e9])
        return out
