"""Decoupled ingest/serve latency: snapshot queries vs the interleaved
baseline, tenant-axis scaling, and the O(1) snapshot swap.

Three sections, all measuring the double-buffered serving architecture
(``core/serving`` + ``engine.StreamBatch.publish``):

* **latency** — B tenants ingesting at capacity M while query
  micro-batches arrive.  Each step an ingest block and a query batch
  arrive together.  The INTERLEAVED baseline answers queries from the
  working state: the transform data-depends on the update, so it MUST be
  scheduled after it and its latency eats the whole fold (that is the
  seed architecture's p99).  The DECOUPLED path answers from the last
  published immutable snapshot — no data dependency on the pending
  block — so the serving loop schedules the query ahead of the ingest
  dispatch (``IngestServeLoop.step`` order) and p99 stays at pure query
  compute.  (On a single-stream device, work queues FIFO per dispatch
  order; decoupling is exactly what makes the query-first order legal.)
  Queries are also timed IDLE (no pending block) — the smoke gate
  requires decoupled-under-ingest p99 <= 3x idle p99 (plus
  finiteness).

* **tenant scaling** — queries/s of ``distributed.make_tenant_query``
  over a (P_t, 1) tenant mesh at P_t in {1, 2}, one subprocess per P_t
  (the host-device override must precede JAX init).  NOTE: device
  parallel speedup needs real cores — ``host_cores`` is recorded, and on
  a single-core container the ratio is expected ~1.0 (both forced host
  devices share one core); the >= 1.6x acceptance number is meaningful
  only when host_cores >= 2.

* **swap** — the publish/swap cost across capacities M.  The swap a
  serving loop pays is the HOST-SIDE cost of rotating buffer references
  and dispatching the cached publish computation (the snapshot
  materializes off the query path).  The claim is that it never touches
  the (M, M) eigvecs — a copying publication would scale quadratically
  in M; the donated publication tracks at worst the O(M·C + M·d)
  snapshot leaves (``swap_scaling_exponent_vs_M`` <= ~1, vs 2 for a
  copy; not exactly 0 on CPU, which inline-executes small dispatches).
  The blocked publish (materialization) is reported for contrast.

Emits ``BENCH_serving.json`` at the repo root.  ``--smoke`` runs toy
sizes, skips the JSON, and exits non-zero on a non-finite result or
decoupled-under-ingest p99 > 3x idle p99 (the ``make bench-smoke``
gate).

CPU only: the tenant-scaling children force host devices, and a child
cannot reach a chip its parent holds, so this is not the chip path
(``chip_smoke.py`` phase B drives the decoupled service on the chip).

    PYTHONPATH=src python -m benchmarks.bench_serving [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

OUT_PATH = Path(__file__).resolve().parent.parent / "BENCH_serving.json"
_MARK = "BENCH_SERVING_RESULT:"


def _pcts(samples) -> dict:
    import numpy as np

    arr = np.asarray(samples, float)
    return {"p50_ms": float(np.percentile(arr, 50)),
            "p99_ms": float(np.percentile(arr, 99)),
            "max_ms": float(arr.max())}


def _latency_section(smoke: bool) -> dict:
    """Query latency under concurrent ingest: decoupled vs interleaved."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import engine as eng, kernels_fn as kf, serving

    if smoke:
        B, M, d, warmup, rounds, nq = 4, 64, 8, 8, 8, 4
    else:
        # warmup puts m just past a bucket crossing (144 -> bucket 256)
        # so the 2*rounds ingested points during timing stay inside one
        # bucket — no recompile spike lands in either path's p99.
        B, M, d, warmup, rounds, nq = 8, 512, 16, 140, 30, 8
    rng = np.random.default_rng(0)
    spec = kf.KernelSpec(name="rbf", sigma=float(d))
    plan = eng.UpdatePlan(matmul="jnp", dispatch="bucketed",
                          serve_components=8)
    sb = eng.StreamBatch(jnp.asarray(rng.normal(size=(B, 4, d)), jnp.float32),
                         M, spec, plan=plan, adjusted=True,
                         dtype=jnp.float32)
    for _ in range(warmup):
        st = sb.update(jnp.asarray(rng.normal(size=(B, d)), jnp.float32))
    jax.block_until_ready(st.L)
    snaps = sb.publish()
    n_comp = plan.serve_components

    # Both serving paths jitted end-to-end, as a real loop would run them:
    # the decoupled query reads the frozen snapshot; the interleaved
    # baseline's transform reads the working state the in-flight update
    # writes, so it queues behind the whole update.
    qfn = jax.jit(lambda s, x: serving.query_batch(s, x, spec=spec,
                                                   plan=plan))
    tfn = jax.jit(lambda s, x: jax.vmap(
        lambda si, xi: eng.transform_state(si, xi, n_components=n_comp,
                                           spec=spec, adjusted=True,
                                           plan=plan))(s, x))

    def qbatch():
        return jnp.asarray(rng.normal(size=(B, nq, d)), jnp.float32)

    jax.block_until_ready(qfn(snaps, qbatch()))
    jax.block_until_ready(tfn(st, qbatch()))

    idle, dec, inter = [], [], []
    for _ in range(rounds):
        q = qbatch()
        # Idle: no update in flight.
        t0 = time.perf_counter()
        jax.block_until_ready(qfn(snaps, q))
        idle.append((time.perf_counter() - t0) * 1e3)

        # Decoupled: block + queries arrive together; the snapshot query
        # has no data dependency on the block, so it is served FIRST
        # (IngestServeLoop.step order), then the ingest is dispatched.
        t0 = time.perf_counter()
        jax.block_until_ready(qfn(snaps, q))
        dec.append((time.perf_counter() - t0) * 1e3)
        st = sb.update(jnp.asarray(rng.normal(size=(B, d)), jnp.float32))
        jax.block_until_ready(st.L)
        snaps = sb.publish()

        # Interleaved baseline: the transform reads the working state the
        # just-dispatched update writes — it queues behind the update.
        st = sb.update(jnp.asarray(rng.normal(size=(B, d)), jnp.float32))
        t0 = time.perf_counter()
        y = tfn(st, q)
        jax.block_until_ready(y)
        inter.append((time.perf_counter() - t0) * 1e3)

    finite = bool(jnp.isfinite(y).all()) and all(
        bool(jnp.isfinite(st.L).all()) for st in sb.working_states())
    out = {
        "tenants": B, "capacity": M, "dim": d, "query_batch": nq,
        "warmup_points": warmup, "rounds": rounds,
        "m_final": int(np.max(np.asarray(sb.states.m))),
        "idle": _pcts(idle), "decoupled": _pcts(dec),
        "interleaved": _pcts(inter),
        "p99_speedup_decoupled":
            _pcts(inter)["p99_ms"] / _pcts(dec)["p99_ms"],
        "p99_under_ingest_over_idle":
            _pcts(dec)["p99_ms"] / _pcts(idle)["p99_ms"],
        "finite": finite,
    }
    print(f"[serving] B={B} M={M}: query p99 idle "
          f"{out['idle']['p99_ms']:.2f} ms, decoupled-under-ingest "
          f"{out['decoupled']['p99_ms']:.2f} ms, interleaved "
          f"{out['interleaved']['p99_ms']:.2f} ms -> "
          f"{out['p99_speedup_decoupled']:.1f}x decoupled p99 win")
    return out


def _swap_section(smoke: bool) -> dict:
    """Publish/swap cost across capacities: the host-side swap must be
    flat in M (O(1)); blocked materialization grows O(M·C + M·d)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import engine as eng
    from repro.core import inkpca, kernels_fn as kf, serving

    Ms = (64, 128) if smoke else (256, 512, 1024)
    d, m_at, rounds = (8, 12, 5) if smoke else (16, 48, 15)
    rng = np.random.default_rng(1)
    spec = kf.KernelSpec(name="rbf", sigma=float(d))
    per_m = []
    for M in Ms:
        X = rng.normal(size=(m_at, d)).astype(np.float32)
        state = inkpca.init_state(jnp.asarray(X[:4]), M, spec, adjusted=True,
                                  dtype=jnp.float32)
        state = eng.Engine(spec, eng.DEFAULT_PLAN._replace(
            dispatch="bucketed")).update_block(state, jnp.asarray(X[4:]))
        buf = serving.DoubleBuffer(state, n_components=8)
        for _ in range(3):                    # reach donation steady state
            jax.block_until_ready(buf.publish(state).S)
        swap_ms, publish_ms = [], []
        for _ in range(rounds):
            t0 = time.perf_counter()
            snap = buf.publish(state)         # dispatch + buffer flip only
            swap_ms.append((time.perf_counter() - t0) * 1e3)
            jax.block_until_ready(snap.S)
            t0 = time.perf_counter()
            jax.block_until_ready(buf.publish(state).S)
            publish_ms.append((time.perf_counter() - t0) * 1e3)
        per_m.append({"capacity": M,
                      "swap_ms": float(np.median(swap_ms)),
                      "publish_blocked_ms": float(np.median(publish_ms))})
        print(f"[serving] M={M}: swap {per_m[-1]['swap_ms']:.3f} ms "
              f"(host flip + dispatch), publish blocked "
              f"{per_m[-1]['publish_blocked_ms']:.3f} ms")
    swaps = [r["swap_ms"] for r in per_m]
    # The O(1)-vs-M claim, checked as a scaling exponent: the swap must
    # track the O(M·C + M·d) snapshot leaves at worst (exponent <= ~1;
    # CPU inline-executes small dispatches, so it isn't exactly 0), and
    # NEVER the (M, M) eigvecs a copying publication would pay
    # (exponent 2).
    exponent = (float(np.log(swaps[-1] / swaps[0])
                      / np.log(Ms[-1] / Ms[0])) if swaps[0] > 0 else 0.0)
    return {"m_active": m_at, "per_capacity": per_m,
            "swap_ratio_max_over_min": max(swaps) / min(swaps),
            "swap_scaling_exponent_vs_M": exponent}


def _worker_scaling(p_tenant: int, smoke: bool) -> dict:
    """Runs in a subprocess with p_tenant forced host devices: aggregate
    queries/s of the tenant-sharded query path."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import distributed as dist, engine as eng
    from repro.core import kernels_fn as kf

    assert jax.device_count() >= p_tenant, (jax.device_count(), p_tenant)
    if smoke:
        B, M, d, warmup, nq, rounds = 4, 64, 8, 6, 4, 10
    else:
        B, M, d, warmup, nq, rounds = 8, 512, 16, 60, 8, 40
    rng = np.random.default_rng(2)
    spec = kf.KernelSpec(name="rbf", sigma=float(d))
    plan = eng.UpdatePlan(matmul="jnp", dispatch="bucketed",
                          serve_components=8)
    sb = eng.StreamBatch(jnp.asarray(rng.normal(size=(B, 4, d)), jnp.float32),
                         M, spec, plan=plan, adjusted=True,
                         dtype=jnp.float32)
    for _ in range(warmup):
        sb.update(jnp.asarray(rng.normal(size=(B, d)), jnp.float32))
    snaps = sb.publish()
    mesh = dist.make_tenant_mesh(p_tenant, 1)
    qfn = dist.make_tenant_query(mesh, spec, plan=plan)
    q = jnp.asarray(rng.normal(size=(B, nq, d)), jnp.float32)
    y = qfn(snaps, q)                          # compile
    jax.block_until_ready(y)
    if not bool(jnp.isfinite(y).all()):
        raise SystemExit(f"[serving] non-finite queries at P_t={p_tenant}")
    t0 = time.perf_counter()
    for _ in range(rounds):
        y = qfn(snaps, q)
        jax.block_until_ready(y)
    total = time.perf_counter() - t0
    qps = B * nq * rounds / total
    print(f"[serving] P_t={p_tenant}: {qps:.0f} queries/s "
          f"({B} tenants x {nq} queries x {rounds} rounds)")
    return {"P_t": p_tenant, "tenants": B, "capacity": M,
            "query_batch": nq, "rounds": rounds, "queries_per_s": qps}


def _tenant_scaling(smoke: bool) -> dict:
    per_pt = []
    for p_t in (1, 2):
        env = dict(os.environ)
        flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (f"{flags} "
                            f"--xla_force_host_platform_device_count={p_t}")
        env["PYTHONPATH"] = (str(Path(__file__).resolve().parent.parent
                                 / "src")
                             + os.pathsep + env.get("PYTHONPATH", ""))
        cmd = [sys.executable, "-m", "benchmarks.bench_serving",
               "--worker", str(p_t)]
        if smoke:
            cmd.append("--smoke")
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              cwd=Path(__file__).resolve().parent.parent)
        sys.stdout.write(proc.stdout.replace(_MARK, "# "))
        sys.stderr.write(proc.stderr)
        if proc.returncode != 0:
            raise SystemExit(f"[serving] worker P_t={p_t} failed "
                             f"(exit {proc.returncode})")
        payload = [ln for ln in proc.stdout.splitlines()
                   if ln.startswith(_MARK)]
        per_pt.append(json.loads(payload[-1][len(_MARK):]))
    ratio = per_pt[1]["queries_per_s"] / per_pt[0]["queries_per_s"]
    cores = os.cpu_count() or 1
    print(f"[serving] tenant-axis scaling P_t=2 vs 1: {ratio:.2f}x "
          f"(host_cores={cores}; the 1.6x target needs >= 2 real cores)")
    return {"per_tenant_axis": per_pt, "qps_ratio_pt2_over_pt1": ratio,
            "host_cores": cores,
            "note": "forced host devices share physical cores; the "
                    ">=1.6x acceptance ratio requires host_cores >= 2"}


def main(smoke: bool = False) -> dict:
    latency = _latency_section(smoke)
    swap = _swap_section(smoke)
    scaling = _tenant_scaling(smoke)
    result = {
        "backend": "cpu", "dtype": "float32",
        "host_cores": os.cpu_count() or 1,
        "latency_under_ingest": latency,
        "snapshot_swap": swap,
        "tenant_scaling": scaling,
    }
    if smoke:
        ratio = latency["p99_under_ingest_over_idle"]
        if not latency["finite"]:
            raise SystemExit("[serving] smoke gate failed: non-finite")
        if ratio > 3.0:
            raise SystemExit(f"[serving] smoke gate failed: decoupled p99 "
                             f"under ingest is {ratio:.1f}x idle (> 3x)")
        print(f"[serving] smoke OK (finite, p99 under ingest "
              f"{ratio:.2f}x idle <= 3x), JSON unchanged")
        return result
    OUT_PATH.write_text(json.dumps(result, indent=2) + "\n")
    print(f"[serving] wrote {OUT_PATH}")
    if latency["p99_speedup_decoupled"] < 5.0:
        print("[serving] WARNING: decoupled p99 win below the 5x gate")
    if scaling["qps_ratio_pt2_over_pt1"] < 1.6 and result["host_cores"] >= 2:
        print("[serving] WARNING: tenant-axis scaling below the 1.6x gate")
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="toy sizes, no JSON, non-zero exit on non-finite "
                         "or p99-under-ingest > 3x idle")
    ap.add_argument("--worker", type=int, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.worker is not None:
        res = _worker_scaling(args.worker, args.smoke)
        print(_MARK + json.dumps(res))
    else:
        main(smoke=args.smoke)
