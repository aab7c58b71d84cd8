"""JAX tracing ms per ingest step: the trace seconds the program booked
to ``kpca.ingest`` and its children (update, publish, probe, heal,
snapshot, reference spectrum) in the window's steps, over the steps."""
from harness import program


def read(run):
    recs = program.window_records(run, "step")
    if not recs or "trace_s" not in recs[0]:
        return None
    return 1e3 * sum(r["trace_s"] for r in recs) / run.steps
