"""Device ms per query call: the ops that ran inside a ``bench.query``
span (a query's result is read back to the host inside its span)."""


def read(run):
    spans = run.trace.spans_named("bench.query")
    ops = [o for o in run.trace.ops if o.span == "bench.query"]
    if not spans or not ops:
        return None
    return sum(o.dur for o in ops) / 1e6 / len(spans)
