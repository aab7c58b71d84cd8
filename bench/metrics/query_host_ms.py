"""Median host wall ms of the program's ``kpca.query`` span
(``IngestServeLoop.query``: tracing and dispatch of the query program;
the read-back of its result is the caller's)."""
from harness import program, stats


def read(run):
    recs = program.window_records(run, "query")
    spans = [r for r in recs or () if r["name"] == "kpca.query"]
    if not spans:
        return None
    return stats.median([program.dur_ms(r) for r in spans])
