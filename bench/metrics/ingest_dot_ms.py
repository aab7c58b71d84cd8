"""Device ms per ingest step of the dot-like ops (the rotations) in the
rank-one update programs."""
from harness import layers


def read(run):
    return layers.per_step_ms(
        run, lambda o: layers.is_ingest(o) and layers.is_dot(o))
