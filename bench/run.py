#!/usr/bin/env python3
"""Benchmark of the windowed KPCA service on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` (a configuration under
``bench/configs`` and a traffic mix under ``bench/traffic``, found by
name) from the root of a checkout: builds the deployment from the seed,
warms the programs the mix uses, offers the mix open-loop for
``--seconds``, drains what was due, and compares the state and the
served answers with the configuration's float64 reference.  Set-up
(``setup_s``) runs from process start to the window's start.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics, read by
``bench/metrics/<name>.py`` from a profiler trace of the window),
``device`` and, last, ``checks``: each number compared with its limit.
The same numbers end standard error.  Without a TPU, or with fewer chips
than the cell asks for, it prints no result and exits 2.  The persistent
compilation cache lives in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path


def _process_age_s() -> float:
    """Seconds since this process started (Linux; 0 elsewhere)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


T_PROCESS0 = time.perf_counter() - _process_age_s()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell, its configuration, its traffic mix and the metric
    entries of ``BENCHMARK.json`` that apply to it."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    limits = json.loads(
        (root / "bench" / "limits" / f"{name}.json").read_text())
    return {"cell": cell, "config": cfg, "traffic": traffic, "e2e": e2e,
            "per_layer": layer, "limits": limits["limits"]}


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Run:
    """What a per-layer reader gets: the reduced trace of the window
    (``trace``, a ``harness.trace.Reduced``), the window's record
    (``window``) and the service's counts (``steps``, ``queries``)."""

    def __init__(self, reduced, window):
        self.trace = reduced
        self.window = window
        self.steps = sum(1 for x in window.s_start if math.isfinite(x))
        self.queries = sum(1 for x in window.q_lat if math.isfinite(x))


def _device_info(devices) -> dict:
    peak = 0
    for d in devices:
        try:
            peak = max(peak, int((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0)))
        except Exception:
            pass
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             config_overrides: dict | None = None,
             traffic_overrides: dict | None = None,
             require_tpu: bool = True, keep_trace: str | None = None,
             out=sys.stdout) -> dict | None:
    """Run one cell and print its result line; returns the result (None
    where no result may be printed)."""
    c = load_cell(name)
    cfg = {**c["config"], **(config_overrides or {})}
    traffic = {**c["traffic"], **(traffic_overrides or {})}
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from harness import cell as hc, check, clock as hclock, data
    from harness import schedule, stats, trace as htrace

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform} devices)",
              file=sys.stderr)
        return None
    chips = int(c["cell"]["chips"])
    if len(devices) < chips:
        print(f"bench: {name} needs {chips} chips, found {len(devices)}",
              file=sys.stderr)
        return None
    devices = devices[:chips]
    clock = hclock.CompileClock()
    mode = schedule.ingest_mode(traffic)
    parts = {"imports_s": time.perf_counter() - T_PROCESS0}
    t = time.perf_counter()
    streams = data.tenant_streams(cfg, seed)
    parts["data_s"] = time.perf_counter() - t
    t = time.perf_counter()
    svc = hc.Service(cfg, streams, seed, traffic)
    parts["init_s"] = time.perf_counter() - t
    parts.update(svc.warm(mode))
    parts["jax_compile_s"] = clock.seconds
    # Long-lived servers freeze what set-up left behind (JAX's caches and
    # compiled programs) so that a full collection in the window does not
    # rescan it; the window's own garbage is still collected.
    gc.collect()
    gc.freeze()
    print("[setup] " + " ".join(f"{k}={v:.3f}" for k, v in parts.items()),
          file=out, flush=True)
    if trace:
        svc.spans_on()
    ev0, tr0 = clock.compiles, clock.traces
    pauses = []
    gc_start = {}

    def gc_watch(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                gc_start[0] = time.perf_counter()
            elif 0 in gc_start:
                pauses.append(time.perf_counter() - gc_start.pop(0))

    gc.callbacks.append(gc_watch)
    cap: dict = {}
    if trace:
        with htrace.capture(cap):
            w = hc.run_window(svc, traffic, seconds, seed,
                              cfg["check"]["sampled_queries"], trace=True)
    else:
        w = hc.run_window(svc, traffic, seconds, seed,
                          cfg["check"]["sampled_queries"])
    compiles, traces = clock.compiles - ev0, clock.traces - tr0
    gc.callbacks.remove(gc_watch)
    gc.unfreeze()
    setup_s = w.t0 - T_PROCESS0
    device = _device_info(devices)
    lag = sorted(w.lag)
    print(f"[generator] wakeups={len(lag)} "
          f"lag_p95_ms={(lag[int(0.95 * (len(lag) - 1))] if lag else 0) * 1e3:.3f} "
          f"lag_max_ms={(lag[-1] if lag else 0) * 1e3:.3f} "
          f"steps={len(w.s_due)} queries={len(w.q_lat)} "
          f"host_traces={traces} gc2={len(pauses)} "
          f"gc2_max_ms={max(pauses, default=0) * 1e3:.1f} "
          f"step_max_ms={max(w.s_serv, default=0) * 1e3:.1f} "
          f"step_p50_ms={stats.median(w.s_serv) * 1e3:.1f} "
          f"query_max_ms={max(w.q_serv, default=0) * 1e3:.1f} "
          f"query_p50_ms={stats.median(w.q_serv) * 1e3:.1f} "
          f"heals={svc.loop.heals} skipped={svc.loop.skipped}",
          file=out, flush=True)
    metrics, breakdown = {}, None
    if trace:
        tr = htrace.extract(cap["path"])
        if keep_trace:
            import shutil

            Path(keep_trace).mkdir(parents=True, exist_ok=True)
            shutil.copy(cap["path"], Path(keep_trace) / f"{name}.xplane.pb")
        htrace.cleanup(cap)
        win = [s for s in tr.spans if s.name == "bench.window"]
        lo = win[0].start if win else min(o.start for o in tr.ops)
        hi = win[0].end if win else max(o.end for o in tr.ops)
        red = htrace.Reduced(tr, lo, hi)
        run = Run(red, w)
        for m in c["per_layer"]:
            v = load_reader(m["name"])(run)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = {"device_ops": red.top_ops(10),
                     "idle_gaps": red.idle_gaps(10)}
    else:
        e2e = hc.end_to_end(w, mode)
        e2e["setup_s"] = setup_s
        for m in c["e2e"]:
            if m["name"] in e2e and math.isfinite(e2e[m["name"]]):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    attempted, failed = hc.counts(w, mode)
    numbers = check.compare(svc, w, compiles, seed, c["limits"])
    correct = check.verdict(numbers)
    for k, (v, lim) in numbers.items():
        print(f"check {k} {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAIL'}", file=sys.stderr)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in numbers.items()}
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="directory to copy the traced run's .xplane.pb to")
    args = ap.parse_args(argv)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    res = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   keep_trace=args.keep_trace)
    return 0 if res is not None else 2


if __name__ == "__main__":
    sys.exit(main())
