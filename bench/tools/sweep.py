#!/usr/bin/env python3
"""Find a cell's knee on the chip: one process builds and warms the
cell's deployment once, then offers its mix at each of several rates for
``--seconds`` each and prints one line per rate.

    python bench/tools/sweep.py --workload magic-8x2048.serve \
        --field ingest.per_s --rates 1 2 3 4 --seconds 15 --seed 7

``--field`` names the traffic parameter swept (``ingest.per_s`` or
``queries.per_s``).  A rate is sustained when the last request due in
the window started within about one service time of its due time: a
backlog that grows through the window shows as a lateness that grows
with the window's length.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import math
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--field", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import run  # noqa: E402  (bench/run.py)

    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(run.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    c = run.load_cell(args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    from harness import cell as hc, data, schedule, stats

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    cfg, traffic = c["config"], c["traffic"]
    mode = schedule.ingest_mode(traffic)
    svc = hc.Service(cfg, data.tenant_streams(cfg, args.seed), args.seed,
                     traffic)
    svc.warm(mode)
    gc.collect()
    gc.freeze()
    group, key = args.field.split(".")
    for rate in args.rates:
        tr = copy.deepcopy(traffic)
        tr[group][key] = rate
        w = hc.run_window(svc, tr, args.seconds, args.seed, 0)
        q_late = [lat for lat in w.q_lat]
        s_late = [s - d for d, s in zip(w.s_due, w.s_start)]
        stale = [p - d for d, p in zip(w.s_due, w.s_pub)]
        out = {"rate": rate, "steps": len(w.s_due), "queries": len(w.q_lat),
               "query_p50_ms": stats.median(q_late) * 1e3,
               "query_p95_ms": stats.percentile(q_late, 95) * 1e3,
               "query_last_ms": (q_late[-1] * 1e3 if q_late else None),
               "step_start_late_last_ms": (s_late[-1] * 1e3 if s_late
                                           else None),
               "staleness_p95_ms": (stats.percentile(stale, 95) * 1e3
                                    if stale else None),
               "e2e": hc.end_to_end(w, mode)}
        print(json.dumps({k: (None if isinstance(v, float)
                              and not math.isfinite(v) else v)
                          for k, v in out.items()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
