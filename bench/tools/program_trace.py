#!/usr/bin/env python3
"""What the program's own spans, counters and stage names show in one
traced run of a cell.

    python bench/tools/program_trace.py --workload <cell> --seed <n> \
        --seconds <s> [--out <file.json>]

Runs the cell in this process as ``bench/run.py --trace 1`` does (its
result line is printed as usual), keeps the profiler trace, and prints
one JSON object last:

- ``clock``: the largest offset, in microseconds, between a program
  span's record on the hub's span ring (``time.time_ns``) and the same
  span's event in the trace (``profile_start_time`` of the trace's
  ``Task Environment`` plane plus the event's offset), at start and end;
- ``stages``: device ms per ingest step of the update and downdate
  programs by stage (``kpca.krow``, ``kpca.solve``, ``kpca.rotate``,
  ``kpca.downdate``, ``unscoped``; ``unmatched`` for an op whose
  instruction the compiled module does not name), their sum beside the
  ingest programs' total; a TPU trace's op events carry the HLO
  instruction without its metadata, so the stage comes from the
  ``op_name`` of the compiled module's HLO text;
- ``idle_gaps``: the longest device-idle gaps, each labelled by the
  innermost span of either kind (``bench.`` or ``kpca.``) and by the
  ``bench.`` span alone, and the idle seconds of every gap by label;
- ``counters``: the change of the hub's ``jax_*`` counters over the
  window, by span, and the ``[generator]`` line's ``host_traces``;
- ``publish``: the median ``kpca.probe`` of the window's publishes and
  the ``publish_host_ms`` and ``publish_ms`` readings;
- ``metrics``, ``end_to_end_traced``: the run's per-layer readings and
  its end-to-end numbers, taken with the profiler on.

Without a TPU it prints no result and exits 2, as ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import bisect
import io
import json
import math
import re
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


class _Tee(io.TextIOBase):
    def __init__(self, *outs):
        self.outs = outs

    def write(self, s):
        for o in self.outs:
            o.write(s)
        return len(s)

    def flush(self):
        for o in self.outs:
            o.flush()


def _host_spans(path: str, prefix: str = "kpca."):
    """(profile_start_time, [(start, end, name, stats)]) of the trace's
    host events whose names start with ``prefix``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    base, out = None, []
    for plane in pd.planes:
        if plane.name == "Task Environment":
            base = int(dict(plane.stats).get("profile_start_time", 0)) or None
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefix):
                        s = int(ev.start_ns)
                        try:
                            st = dict(ev.stats)
                        except Exception:
                            st = {}
                        out.append((s, s + int(ev.duration_ns), ev.name, st))
    return base, out


def clock_offsets(records, events, base) -> dict:
    """Largest |ring − trace| offsets (us) of the spans found in both,
    matched by name and ids in order."""
    by: dict = {}
    for s, e, name, st in sorted(events):
        by.setdefault((name, st.get("step"), st.get("query")), []).append(
            (s, e))
    starts, ends = [], []
    for r in records:
        lst = by.get((r["name"], r.get("step"), r.get("query")))
        if not lst or base is None:
            continue
        s, e = lst.pop(0)
        starts.append((r["start_ns"] - (base + s)) / 1e3)
        ends.append((r["end_ns"] - (base + e)) / 1e3)
    if not starts:
        return {"matched": 0}
    return {"matched": len(starts),
            "start_us_max_abs": max(abs(x) for x in starts),
            "end_us_max_abs": max(abs(x) for x in ends),
            "start_us_mean": sum(starts) / len(starts),
            "end_us_mean": sum(ends) / len(ends)}


def compiled_stage_maps(svc) -> dict:
    """Module name -> {instruction: stage} for the update and downdate
    programs at the service's working shapes (the compile is a cache
    hit for the executables the window ran)."""
    import jax.numpy as jnp

    from harness import program
    from repro.core import engine as eng

    st = svc.batch.working_states()[0]
    kp = svc.plan.kernel_plan()
    adj = bool(svc.cfg["adjusted"])
    B, d = svc.B, svc.x32.shape[-1]
    upd = eng._batched_update.lower(
        st, jnp.zeros((B, d), svc.x32.dtype), svc.spec, adj, kp)
    dn = eng._batched_downdate_masked.lower(
        st, jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool), svc.spec,
        adj, kp)
    return {"_batched_update": program.hlo_stages(upd.compile().as_text()),
            "_batched_downdate": program.hlo_stages(dn.compile().as_text())}


def stage_ms(red, maps: dict, steps: int) -> dict:
    """Device ms per step of the ingest programs' ops, by stage."""
    from harness import layers, program

    acc: dict = {}
    for o in red.ops:
        if not layers.is_ingest(o):
            continue
        smap = next((m for k, m in maps.items() if k in o.module), {})
        stage = smap.get(program.instruction(o.name), "unmatched")
        acc[stage] = acc.get(stage, 0) + o.dur
    if not steps:
        return {}
    out = {k: v / 1e6 / steps for k, v in sorted(acc.items())}
    out["sum"] = sum(acc.values()) / 1e6 / steps
    return out


def innermost(spans, starts, t, depth: int = 64) -> str:
    """Name of the innermost of nested-or-disjoint spans holding t."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(i - depth, -1), -1):
        s = spans[j]
        if s.start <= t < s.end:
            return s.name
    return ""


def labelled_gaps(red, kpca_spans, n: int = 10) -> dict:
    """The longest idle gaps by the innermost span of either kind, and
    idle seconds of all gaps by that label."""
    from harness import trace as htrace

    if not red.devices:
        return {}
    dev = red.devices[0]
    iv = [(o.start, o.end) for o in red.trace.ops if o.device == dev]
    window = [s for s in red.trace.spans if s.name == "bench.window"]
    inner = [s for s in red.trace.spans if s.name != "bench.window"]
    both = sorted(inner + kpca_spans, key=lambda s: (s.start, -s.end))
    bench = sorted(inner, key=lambda s: (s.start, -s.end))
    bs, cs = [s.start for s in bench], [s.start for s in both]

    def label(spans, starts, t):
        got = innermost(spans, starts, t)
        if got:
            return got
        return "bench.window" if any(w.start <= t < w.end
                                     for w in window) else "none"

    by_label: dict = {}
    top = []
    for s, e in sorted(htrace.gaps(iv, red.lo, red.hi),
                       key=lambda g: g[0] - g[1]):
        mid = (s + e) // 2
        lab = label(both, cs, mid)
        by_label[lab] = by_label.get(lab, 0.0) + (e - s) / 1e9
        if len(top) < n:
            top.append([lab, label(bench, bs, mid), (e - s) / 1e9])
    return {"top": top,
            "idle_s_by_label": dict(sorted(by_label.items(),
                                           key=lambda kv: -kv[1]))}


def counter_delta(before: dict, after: dict) -> dict:
    keys = sorted(k for k in after if k.startswith("jax_"))
    d = {k: after[k] - before.get(k, 0.0) for k in keys}
    return {k: v for k, v in d.items() if v}


def analyse(name: str, seed: int, seconds: float, out=sys.stdout,
            **run_kw) -> dict:
    import run as bench_run
    from harness import cell as hc, program, stats, trace as htrace
    from repro import obs

    got: dict = {}
    inner_window = hc.run_window

    def run_window(svc, *a, **k):
        got["svc"] = svc
        got["before"] = obs.get_hub().scrape()
        w = inner_window(svc, *a, **k)
        got["after"] = obs.get_hub().scrape()
        got["ring"] = list(obs.get_hub().spans)
        got["w"] = w
        return w

    keep = tempfile.mkdtemp(prefix="program-trace-")
    buf = io.StringIO()
    hc.run_window = run_window
    try:
        res = bench_run.run_cell(name, seed, seconds, True, keep_trace=keep,
                                 out=_Tee(buf, out), **run_kw)
    finally:
        hc.run_window = inner_window
    if res is None:
        shutil.rmtree(keep, ignore_errors=True)
        return {}
    path = str(Path(keep) / f"{name}.xplane.pb")
    tr = htrace.extract(path)
    base, kev = _host_spans(path)
    shutil.rmtree(keep, ignore_errors=True)
    win = [s for s in tr.spans if s.name == "bench.window"]
    lo = win[0].start if win else min(o.start for o in tr.ops)
    hi = win[0].end if win else max(o.end for o in tr.ops)
    red = htrace.Reduced(tr, lo, hi)
    run = bench_run.Run(red, got["w"])
    ring = got["ring"]
    steps = program.window_records(run, "step", ring) or []
    queries = program.window_records(run, "query", ring) or []
    kspans = [htrace.Span(s, e, n) for s, e, n, _ in kev if lo <= s < hi]
    result: dict = {"workload": name, "seed": seed,
                    "clock": clock_offsets(steps + queries, kev, base)}
    if any(r["name"] == "kpca.update" for r in steps):
        maps = compiled_stage_maps(got["svc"])
        from harness import layers

        result["stages"] = stage_ms(red, maps, run.steps)
        result["stages"]["ingest_total"] = layers.per_step_ms(
            run, layers.is_ingest)
        result["stages"]["instructions_named"] = {
            k: len(v) for k, v in maps.items()}
    result["idle_gaps"] = labelled_gaps(red, kspans)
    m = re.search(r"host_traces=(\d+)", buf.getvalue())
    result["counters"] = {
        "delta": counter_delta(got["before"], got["after"]),
        "host_traces": int(m.group(1)) if m else None}
    d = result["counters"]["delta"]
    result["counters"]["traces_all_spans"] = sum(
        v for k, v in d.items() if k.startswith("jax_traces_total"))
    probe = [program.dur_ms(r) for r in steps if r["name"] == "kpca.probe"
             and r["parent"] == "kpca.publish"]
    result["publish"] = {
        "probe_ms_median": stats.median(probe) if probe else None,
        "publish_host_ms": bench_run.load_reader("publish_host_ms")(run),
        "publish_ms": bench_run.load_reader("publish_ms")(run)}
    result["metrics"] = {k: v["value"] for k, v in res["metrics"].items()}
    from harness import schedule

    result["end_to_end_traced"] = hc.end_to_end(
        got["w"], schedule.ingest_mode(bench_run.load_cell(name)["traffic"]))
    result["correct"] = res["correct"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None,
                    help="also write the JSON object to this file")
    args = ap.parse_args(argv)
    import jax

    import run as bench_run

    jax.config.update("jax_compilation_cache_dir",
                      str(bench_run.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    result = analyse(args.workload, args.seed, args.seconds)
    if not result:
        return 2
    line = json.dumps(result, default=lambda x: None if (
        isinstance(x, float) and math.isnan(x)) else str(x))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
