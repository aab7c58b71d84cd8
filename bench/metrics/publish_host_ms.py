"""Median over the window's publishes of the ``kpca.publish`` span's
wall ms outside its ``kpca.probe`` children: the host work of a publish
(snapshot and reference-spectrum dispatch, their tracing) after the
probe's read-back has waited for the step's device work."""
from harness import program, stats


def read(run):
    recs = program.window_records(run, "step")
    if not recs:
        return None
    probe: dict = {}
    for r in recs:
        if r["name"] == "kpca.probe" and r["parent"] == "kpca.publish":
            probe[r["step"]] = probe.get(r["step"], 0.0) + program.dur_ms(r)
    host = [program.dur_ms(r) - probe.get(r["step"], 0.0)
            for r in recs if r["name"] == "kpca.publish"]
    return stats.median(host) if host else None
