"""Median wall ms of the host span around ``IngestServeLoop.publish``.
The publish's health probe reads its verdict back to the host, so the
span also holds the wait for the step's device work still in flight."""
from harness import stats


def read(run):
    spans = run.trace.spans_named("bench.publish")
    if not spans:
        return None
    return stats.median([(s.end - s.start) / 1e6 for s in spans])
