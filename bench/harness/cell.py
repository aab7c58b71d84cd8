"""One cell: set-up, the open-loop window, the drain and the check.

The service is the program's multi-tenant windowed KPCA:
``StreamBatch`` (tenants vmapped, one sliding window each) under
``IngestServeLoop`` (ingest into the working state, publish an immutable
snapshot, queries against the published snapshot).  The harness calls
only ``IngestServeLoop.ingest`` (which publishes, ``serve_every=1``) and
``IngestServeLoop.query``, and reads the published snapshot and the
working states after the window for the check.

Timing, all on the host's clock (``time.perf_counter``):

- a query is one ``query`` call of ``rows`` rows per tenant, timed from
  its due time to its result on the host;
- an ingest step folds one point per tenant; its points are due at the
  step's due time (open mix) and count as served when ``ingest`` has
  returned from the publish that includes them and the snapshot is on
  the device (staleness);
- the loop serves every due query first, then one due ingest step, as
  ``IngestServeLoop.step`` orders them; a backlog drains in order.  After
  the window it drains what was due inside it, up to the mix's
  ``drain_s``; what is still not served then has failed.
"""
from __future__ import annotations

import contextlib
import math
import time

import numpy as np

from . import schedule, stats


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class Service:
    """The deployment of a configuration, built and warmed in set-up."""

    def __init__(self, cfg: dict, streams: np.ndarray, seed: int,
                 traffic: dict):
        import jax
        import jax.numpy as jnp

        from repro.core import engine as eng, health as hl
        from repro.core import kernels_fn as kf
        from repro.launch.serve import IngestServeLoop

        self.cfg = cfg
        self.B, self.W = cfg["tenants"], cfg["window"]
        self.C = cfg["serve_components"]
        self.rows = int(traffic["queries"]["rows"]) if traffic.get(
            "queries") else 0
        self.streams = streams                       # (B, n, d) float64
        self.x32 = streams.astype(np.float32)
        n = streams.shape[1]
        self.held0 = n - cfg["held_out"]             # queries come from here
        self.spec = kf.KernelSpec(name=cfg["kernel"], sigma=float(
            cfg["sigma"]))
        policy = hl.DEFAULT_POLICY if cfg["health"] == "default" else None
        # Every path-choosing field of the plan is the program's default.
        self.plan = eng.UpdatePlan(window=self.W, health=policy,
                                   serve_every=cfg["serve_every"],
                                   serve_components=self.C)
        self.batch = eng.StreamBatch(
            jnp.asarray(self.x32[:, :self.W]), cfg["capacity"], self.spec,
            plan=self.plan, adjusted=cfg["adjusted"],
            dtype=getattr(jnp, cfg["dtype"]), window=self.W)
        self.loop = IngestServeLoop(self.batch, self.spec, plan=self.plan,
                                    n_components=self.C)
        jax.block_until_ready(self.loop.snaps)
        self.steps = 0              # ingest steps folded (warm-up included)
        self.published = 0          # steps inside the current snapshot
        self.publishes = 0          # ingest steps that published
        self.rng = np.random.default_rng([seed, 1])

    # -- requests ---------------------------------------------------------
    def next_points(self) -> np.ndarray:
        j = self.W + self.steps
        if j >= self.held0:
            raise RuntimeError(f"stream exhausted after {self.steps} steps")
        return self.x32[:, j]

    def make_query(self) -> np.ndarray:
        idx = self.rng.integers(self.held0, self.streams.shape[1],
                                size=(self.B, self.rows))
        return np.take_along_axis(self.x32, idx[:, :, None], axis=1)

    def query(self, q: np.ndarray, trace: bool):
        with _span("bench.query", trace):
            y = np.asarray(self.loop.query(q))
        return y, self.published

    def ingest(self, trace: bool) -> bool:
        """One step; True when a publish that holds it has landed."""
        import jax

        xs = self.next_points()
        with _span("bench.ingest", trace):
            published = self.loop.ingest(xs)
            jax.block_until_ready(self.loop.snaps)
        self.steps += 1
        if published:
            self.publishes += 1
            self.published = self.steps
        return published

    def spans_on(self):
        """Host spans around the service's publish and update in a traced
        run: the loop calls both through its own attributes."""
        import jax

        loop, batch = self.loop, self.batch
        pub, upd = loop.publish, batch.update

        def publish(*a, **k):
            with jax.profiler.TraceAnnotation("bench.publish"):
                return pub(*a, **k)

        def update(*a, **k):
            with jax.profiler.TraceAnnotation("bench.update"):
                return upd(*a, **k)

        loop.publish, batch.update = publish, update

    def warm(self, mode: str) -> dict:
        """Run each program the mix uses once: one ingest step (with its
        publish) where the mix ingests, one query where it queries.
        Returns the seconds each took."""
        out = {}
        if mode != "none":
            t = time.perf_counter()
            self.ingest(False)
            out["warm_ingest_s"] = time.perf_counter() - t
        if self.rows:
            t = time.perf_counter()
            self.query(self.make_query(), False)
            out["warm_query_s"] = time.perf_counter() - t
        return out


class Window:
    """What the window and its drain recorded."""

    def __init__(self):
        self.q_due = np.zeros((0,))
        self.q_lat = []             # seconds, inf for unserved
        self.q_kept = {}            # sampled query index -> (q, y, steps)
        self.s_due = []             # per ingest step: due time
        self.s_start = []
        self.s_pub = []             # time its points were published
        self.t0 = self.t_end = 0.0
        self.lag = []               # how late the loop woke for a due event
        self.q_serv = []            # seconds each query call took
        self.s_serv = []            # seconds each ingest step took
        self.points_per_step = 0


QUERY_POOL = 256


def run_window(svc: Service, traffic: dict, seconds: float, seed: int,
               sample_n: int, trace: bool = False) -> Window:
    """Offer the mix for ``seconds`` and drain what was due in it."""
    mode = schedule.ingest_mode(traffic)
    rng = np.random.default_rng([seed, 2])
    w = Window()
    w.points_per_step = svc.B
    qspec = traffic.get("queries")
    q_off = (schedule.offsets(qspec, seconds, rng) if qspec
             else np.zeros((0,)))
    s_off = (schedule.offsets(traffic["ingest"], seconds, rng)
             if mode == "open" else np.zeros((0,)))
    nq, ns = q_off.size, s_off.size
    keep = set(rng.choice(nq, size=min(sample_n, nq), replace=False)
               .tolist()) if nq else set()
    # A pool of distinct query batches, used in turn: the service keeps no
    # result between calls, so a repeat costs what a new batch costs.
    pool = [svc.make_query() for _ in range(min(nq, QUERY_POOL))]
    drain = float(traffic.get("drain_s", 60.0))
    lat = [math.inf] * nq
    step_start, step_pub, step_due = [], [], []
    pending = []                       # step indices not yet published
    qi = si = 0
    closed_done = mode != "closed"
    t0 = time.perf_counter()
    q_due, s_due = t0 + q_off, t0 + s_off
    t_end = t0 + seconds
    t_stop = t_end + drain
    with _span("bench.window", trace):
        while True:
            now = time.perf_counter()
            if now >= t_stop:
                break
            while qi < nq and q_due[qi] <= now < t_stop:
                q = pool[qi % len(pool)]
                y, pub = svc.query(q, trace)
                w.q_serv.append(time.perf_counter() - now)
                now = time.perf_counter()
                lat[qi] = now - q_due[qi]
                if qi in keep:
                    w.q_kept[qi] = (q, y, pub)
                qi += 1
            if not closed_done and now >= t_end:
                closed_done = True
            if si < ns and s_due[si] <= now:
                due = s_due[si]
                si += 1
            elif not closed_done:
                due = now
            else:
                due = None
            if due is not None:
                step_due.append(due)
                step_start.append(now)
                step_pub.append(math.inf)
                pending.append(len(step_due) - 1)
                published = svc.ingest(trace)
                t = time.perf_counter()
                w.s_serv.append(t - now)
                if published:
                    for k in pending:
                        step_pub[k] = t
                    pending = []
                continue
            if qi >= nq and si >= ns and closed_done:
                break
            if not closed_done:
                continue
            due_next = [t_stop]
            if qi < nq:
                due_next.append(q_due[qi])
            if si < ns:
                due_next.append(s_due[si])
            nxt = min(due_next)
            if nxt > now:
                with _span("bench.wait", trace):
                    time.sleep(nxt - now)
                if nxt < t_stop:
                    w.lag.append(max(time.perf_counter() - nxt, 0.0))
    # Steps due in the window that never started have failed too.
    for k in range(si, ns):
        step_due.append(s_due[k])
        step_start.append(math.inf)
        step_pub.append(math.inf)
    w.t0, w.t_end = t0, t_end
    w.q_due, w.q_lat = q_off, lat
    w.s_due, w.s_start, w.s_pub = step_due, step_start, step_pub
    return w


def end_to_end(w: Window, mode: str) -> dict:
    """The window's end-to-end numbers, by metric name."""
    out = {}
    if len(w.q_lat):
        out["query_p95_ms"] = stats.percentile(w.q_lat, 95.0) * 1e3
    if mode == "open":
        stale = [p - d for d, p in zip(w.s_due, w.s_pub)
                 for _ in range(w.points_per_step)]
        if stale:
            out["staleness_p95_ms"] = stats.percentile(stale, 95.0) * 1e3
    if mode == "closed":
        done = [p for p in w.s_pub if math.isfinite(p)]
        if done and done[-1] > w.t0:
            out["ingest_pts_per_s"] = (len(done) * w.points_per_step
                                       / (max(done) - w.t0))
    return out


def counts(w: Window, mode: str) -> tuple[int, int]:
    """(attempted, failed) requests: queries due in the window and the
    points of the ingest steps due (open) or started (closed) in it."""
    q_fail = sum(1 for x in w.q_lat if not math.isfinite(x))
    pts = len(w.s_due) * w.points_per_step
    p_fail = sum(w.points_per_step for x in w.s_pub
                 if not math.isfinite(x))
    return len(w.q_lat) + pts, q_fail + p_fail
