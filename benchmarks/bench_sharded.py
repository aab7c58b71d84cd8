"""Sharded rank-one update throughput: square-block vs rectangular-pruned.

The PR-2 sharded path rotated each device's FULL (M/P, M) row block against
a dense (M, M) factor — active-tile pruning was lost the moment P > 1
because the Pallas kernels required square operands.  The rectangular
kernels (+ the bucketed local slice in ``core/distributed.py``) restore
m-scaling at any P: each device rotates a (min(M/P, M_b), M_b) rectangle
and the replicated secular solve runs at O(M_b²·iters).

Three comparisons per device count P ∈ {1, 2, 4} (CPU devices via the
``--xla_force_host_platform_device_count`` XLA flag, one subprocess per P
since the flag must be set before JAX initializes):

* ``square``   — ``make_sharded_update`` with the fixed-dispatch plan
                 (the PR-2 square-block path: O(M³/P) regardless of m).
* ``rect``     — the same update with ``dispatch="bucketed"``: the
                 rectangular-pruned path, O(M_b²·m/P) rotation work.
* ``pair_fallback_{on,off}`` — the fused ±sigma sharded pair with and
                 without the collective-balanced merge fallback (the
                 fallback costs one extra O(M) psum and a cond).

Emits ``BENCH_sharded.json`` at the repo root.  ``--smoke`` runs toy
sizes, skips the JSON, and exits non-zero on non-finite output (the
``make bench-smoke`` gate).

CPU only: each child process forces P host devices, and a child cannot
reach a chip its parent holds, so this is not the chip path.  The
row-sharded window step runs on four TPU chips through
``python chip_smoke.py --four-chips``.

    PYTHONPATH=src python -m benchmarks.bench_sharded [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

OUT_PATH = Path(__file__).resolve().parent.parent / "BENCH_sharded.json"
_MARK = "BENCH_SHARDED_RESULT:"


def _worker(P: int, smoke: bool) -> dict:
    """Runs inside a subprocess with P forced host devices."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import distributed as dkpca, engine as eng, rankone
    from repro.distributed.sharding import make_mesh

    assert jax.device_count() >= P, (jax.device_count(), P)
    if smoke:
        M, m, rounds, min_bucket = 64, 12, 3, 16
    else:
        M, m, rounds, min_bucket = 512, 64, 15, 128

    rng = np.random.default_rng(0)
    A = rng.normal(size=(m, m))
    A = A @ A.T
    lam, vec = np.linalg.eigh(A)
    L = np.zeros(M, np.float32)
    U = np.eye(M, dtype=np.float32)
    L[:m] = lam
    U[:m, :m] = vec
    L = rankone.sentinelize(jnp.asarray(L), jnp.int32(m), jnp.float32(0.0))
    U = jnp.asarray(U)

    def vvec(seed):
        v = np.zeros(M, np.float32)
        v[:m] = np.random.default_rng(seed).normal(size=m)
        return jnp.asarray(v)

    mesh = make_mesh((P,), ("data",))
    mj = jnp.int32(m)

    def _median_time(fn, args_of_round) -> float:
        out = fn(*args_of_round(0))            # compile
        jax.block_until_ready(out)
        ts = []
        for r in range(rounds):
            args = args_of_round(r + 1)
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            ts.append(time.perf_counter() - t0)
        if not all(bool(jnp.isfinite(o).all()) for o in out):
            raise SystemExit(f"[sharded] non-finite output at P={P}")
        return float(np.median(ts))

    plans = {
        "square": eng.UpdatePlan(dispatch="fixed", matmul="jnp"),
        "rect": eng.UpdatePlan(dispatch="bucketed", matmul="jnp",
                               min_bucket=min_bucket),
    }
    res: dict = {"P": P, "M": M, "m": m, "rounds": rounds,
                 "min_bucket": min_bucket}
    for name, plan in plans.items():
        upd = dkpca.make_sharded_update(mesh, plan=plan)
        res[f"update_s_{name}"] = _median_time(
            upd, lambda r: (L, U, vvec(r), jnp.float32(1.3), mj))
    res["speedup_rect"] = res["update_s_square"] / res["update_s_rect"]

    for name, fb in (("on", True), ("off", False)):
        plan = eng.UpdatePlan(dispatch="bucketed", matmul="jnp2",
                              min_bucket=min_bucket, merge_fallback=fb)
        pair = dkpca.make_sharded_update_pair(mesh, plan=plan)
        res[f"pair_s_fallback_{name}"] = _median_time(
            pair, lambda r: (L, U, vvec(2 * r), jnp.float32(1.3),
                             vvec(2 * r + 1), jnp.float32(-1.3), mj))
    res["fallback_overhead"] = (res["pair_s_fallback_on"]
                                / res["pair_s_fallback_off"])
    print(f"[sharded] P={P} M={M} m={m}: square "
          f"{res['update_s_square'] * 1e3:.1f} ms, rect-pruned "
          f"{res['update_s_rect'] * 1e3:.1f} ms -> "
          f"{res['speedup_rect']:.1f}x; fused pair fallback on/off "
          f"{res['pair_s_fallback_on'] * 1e3:.1f}/"
          f"{res['pair_s_fallback_off'] * 1e3:.1f} ms")
    return res


def main(smoke: bool = False) -> dict:
    # Smoke gates one multi-device config only: compile time dominates at
    # toy sizes, and P=2 already exercises psums, slicing and the cond.
    device_counts = (2,) if smoke else (1, 2, 4)
    per_p = []
    for P in device_counts:
        env = dict(os.environ)
        flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (f"{flags} "
                            f"--xla_force_host_platform_device_count={P}")
        env["PYTHONPATH"] = (str(Path(__file__).resolve().parent.parent
                                 / "src")
                             + os.pathsep + env.get("PYTHONPATH", ""))
        cmd = [sys.executable, "-m", "benchmarks.bench_sharded",
               "--worker", str(P)]
        if smoke:
            cmd.append("--smoke")
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              cwd=Path(__file__).resolve().parent.parent)
        sys.stdout.write(proc.stdout.replace(_MARK, "# "))
        sys.stderr.write(proc.stderr)
        if proc.returncode != 0:
            raise SystemExit(f"[sharded] worker P={P} failed "
                             f"(exit {proc.returncode})")
        payload = [ln for ln in proc.stdout.splitlines()
                   if ln.startswith(_MARK)]
        per_p.append(json.loads(payload[-1][len(_MARK):]))

    result = {"backend": "cpu", "dtype": "float32", "per_device_count": per_p}
    if not smoke:
        OUT_PATH.write_text(json.dumps(result, indent=2) + "\n")
        print(f"[sharded] wrote {OUT_PATH}")
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="toy sizes, no JSON, non-zero exit on non-finite")
    ap.add_argument("--worker", type=int, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.worker is not None:
        res = _worker(args.worker, args.smoke)
        print(_MARK + json.dumps(res))
    else:
        main(smoke=args.smoke)
