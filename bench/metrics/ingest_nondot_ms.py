"""Device ms per ingest step of every other op in the rank-one update
programs (secular solve, Gu-Eisenstat, deflation, kernel rows)."""
from harness import layers


def read(run):
    return layers.per_step_ms(
        run, lambda o: layers.is_ingest(o) and not layers.is_dot(o))
