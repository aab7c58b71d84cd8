"""JAX tracing ms per query served in the window: the trace seconds the
program booked to its ``kpca.query`` spans (the counter
``jax_trace_seconds_total{span="kpca.query"}``, summed over the window's
query spans), over the queries served."""
from harness import program


def read(run):
    recs = program.window_records(run, "query")
    spans = [r for r in recs or () if r["name"] == "kpca.query"]
    if not spans or "trace_s" not in spans[0]:
        return None
    return 1e3 * sum(r["trace_s"] for r in spans) / run.queries
