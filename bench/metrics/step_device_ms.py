"""Device ms per service step of the ingest programs (windowed downdate
and update, all tenants)."""
from harness import layers


def read(run):
    return layers.per_step_ms(run, layers.is_ingest)
