"""Device program launches per service step: program runs on the device
that started inside a step's ``bench.ingest`` span (update, publish and
their eager operations)."""
from harness import layers


def read(run):
    mods = [m for m in run.trace.modules if m.span in layers.INGEST_SPANS]
    if not run.steps or not mods:
        return None
    return len(mods) / run.steps
