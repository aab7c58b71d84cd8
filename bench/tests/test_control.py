"""The lower-precision control of the check that decides ``correct``.

The configurations state float32 with every eigensystem contraction at
HIGHEST matmul precision (six bfloat16 passes on a TPU).  The control is
the program with its own precision setting one step lower, HIGH (three
passes): ``repro.core.precision.MATMUL_PRECISION``, as every module of
the program bound it at import, set to HIGH before anything is traced.
A sound check reads such a run as not correct on at least one number.

On a CPU every precision computes float32 dots exactly, so the control
changes nothing there: the test needs a TPU and skips without one.  On
the chip, at a cell's own size:

    python bench/tests/test_control.py --workload magic-8x2048.serve \
        --seeds 11 12 13 --seconds 30

runs each seed in a process of its own and prints each run's result
line, with its numbers beside their limits.
"""
import argparse
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]


def lower_precision(precision=None) -> int:
    """Set the program's matmul precision in every module that bound it;
    returns how many modules were changed.  Call before any tracing."""
    import importlib
    import pkgutil

    import jax

    import repro

    precision = precision or jax.lax.Precision.HIGH
    for info in pkgutil.walk_packages(repro.__path__, "repro."):
        if info.name.startswith(("repro.core", "repro.kernels")):
            importlib.import_module(info.name)
    n = 0
    for name, mod in list(sys.modules.items()):
        if name.startswith("repro") and hasattr(mod, "MATMUL_PRECISION"):
            mod.MATMUL_PRECISION = precision
            n += 1
    return n


def _control_main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--one", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if not args.one:
        rc = 0
        for seed in args.seeds:
            r = subprocess.run([sys.executable, __file__, "--one",
                                "--workload", args.workload, "--seeds",
                                str(seed), "--seconds", str(args.seconds)])
            rc = rc or r.returncode
        return rc
    sys.path.insert(0, str(BENCH))
    import run as bench_run

    sys.path.insert(0, str(bench_run.ROOT / "src"))
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(bench_run.ROOT / ".jax_cache"))
    n = lower_precision()
    print(f"[control] matmul precision HIGH in {n} modules", flush=True)
    res = bench_run.run_cell(args.workload, args.seeds[0], args.seconds,
                             False)
    return 0 if res is not None else 2


def test_control_fails_the_check():
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("precision changes nothing off a TPU: the control "
                    "runs on the chip (see this file's docstring)")
    out = subprocess.run(
        [sys.executable, __file__, "--workload", "magic-8x2048.serve",
         "--seeds", "5", "--seconds", "10"],
        capture_output=True, text=True, timeout=900)
    import json

    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert not res["correct"]


if __name__ == "__main__":
    sys.exit(_control_main())
