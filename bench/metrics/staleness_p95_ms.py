"""95th percentile, over every point due in the window, of the time from
its due time to the return of the first publish that holds it (host
clock, as the end-to-end metrics are taken).  A per-layer reading: across
seeds its spread is wider than any bound the benchmark may set, so it is
not an end-to-end metric (PERF.md, section 2).  It is read in the traced
run, where the profiler's own stalls inside the publish lengthen some
steps, so it reads higher than an untraced run would."""
from harness import cell


def read(run):
    return cell.end_to_end(run.window, "open").get("staleness_p95_ms")
