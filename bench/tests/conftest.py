"""The benchmark's own tests: CPU checks of the harness at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

# Sizes at which a whole cell runs on the CPU in seconds: the cell's code
# path, its checks and its reference, with tiny windows and few rows.
TINY = {"n": 700, "capacity": 64, "window": 48, "held_out": 100}
TINY_TRAFFIC = {
    "magic-8x2048.serve": {
        "ingest": {"mode": "open", "process": "fixed", "per_s": 5.0},
        "queries": {"process": "poisson", "per_s": 20.0, "rows": 16}},
    "magic-w8192.ingest": {},
    "magic-8x2048.read": {
        "queries": {"process": "poisson", "per_s": 50.0, "rows": 16}},
}


def tiny_config(cell: str) -> dict:
    out = dict(TINY)
    out["tenants"] = 1 if cell.startswith("magic-w") else 2
    return out
