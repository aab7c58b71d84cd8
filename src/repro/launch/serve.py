"""Batched serving drivers.

Two serving workloads share this entry point:

* ``--mode lm`` (default): decode loop with KV/recurrent caches.
  CPU-runnable on smoke configs; the same step function is what the
  decode_32k / long_500k dry-run cells lower for the production mesh.

      PYTHONPATH=src python -m repro.launch.serve --arch qwen3_32b --smoke \
          --batch 4 --prompt-len 16 --gen 32

* ``--mode kpca``: streaming incremental-KPCA ingest + transform service.
  Points arrive one at a time; each is folded into the eigendecomposition
  (Algorithm 2) and every ``--transform-every`` points a batch of queries
  is projected on the current principal components.  All dispatch policy
  is carried by one ``engine.UpdatePlan``: ``--dispatch bucketed`` runs
  early-stream updates at the active bucket's O(M_b³), not capacity O(M³)
  (the per-update latencies printed at the end show the staircase), and
  ``--tenants B`` serves B independent streams through the vmapped
  ``engine.StreamBatch`` — one device step folds a point into every
  tenant, instead of B Python-loop dispatches.  ``--cohorts bucket``
  shards a mixed-size cohort into bucket-homogeneous groups: each group
  runs its vmapped step at its OWN bucket M_b, so small tenants stop
  paying the largest tenant's O(M³).

      PYTHONPATH=src python -m repro.launch.serve --mode kpca \
          --capacity 512 --points 200 --dispatch bucketed
      PYTHONPATH=src python -m repro.launch.serve --mode kpca \
          --capacity 512 --points 200 --tenants 8 --dispatch bucketed

  ``--window W`` turns every stream (single and multi-tenant) into a
  sliding window over the trailing W points: ingest past a full window
  first evicts the oldest point through the decremental pipeline
  (``core/downdate.py``), so the service runs forever in bounded memory
  instead of exhausting capacity.

  Every ingest below — single-stream, windowed, guarded, metered, and
  their combinations — is one spelling of the composed
  ``engine.Engine.step``/``step_block`` pipeline: the plan flags select
  the gate/evict/note stages at trace time, so this driver never has to
  pick a ``*_guarded``/``*_metered`` variant by hand.

  ``--decouple`` switches to the double-buffered snapshot architecture
  (``core/serving``): ingest folds blocks into working state A while
  ``--query-rate`` query micro-batches per step read the last PUBLISHED
  immutable snapshot B, republished every ``--serve-every`` blocks with
  an O(1) buffer swap.  Queries never wait on the in-flight update —
  the decoupled p99 stays flat where the interleaved baseline's rides
  every update.  ``--mesh PtxPr`` tenant-shards the query path over a
  (tenant, data) 2-D device mesh (``core/distributed``).

      PYTHONPATH=src python -m repro.launch.serve --mode kpca --decouple \
          --capacity 512 --points 200 --tenants 8 --query-rate 2

* ``--mode nystrom``: streaming landmark-lifecycle service.  Points
  arrive one at a time as observed rows; ``--landmark-policy append``
  admits every point as a landmark until the budget fills (the paper's
  §4 loop), while ``--landmark-policy leverage`` admits on projection
  residual, replaces the lowest-leverage landmark when at budget, and
  stops admitting once the incremental ``trace_error`` trend plateaus
  (the sufficient-subset rule).

      PYTHONPATH=src python -m repro.launch.serve --mode nystrom \
          --capacity 128 --points 300 --landmark-policy leverage
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs, obs
from repro.data.synthetic import TokenStream
from repro.distributed import sharding as shd
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import lm


def _make_plan(args):
    from repro.core import engine as eng

    health = None
    if getattr(args, "health", False):
        from repro.core import health as hl

        health = hl.DEFAULT_POLICY
    # Any export surface implies the in-graph metric lane; --metrics turns
    # it on without one (counters still land in the printed report).
    metrics = bool(getattr(args, "metrics", False)
                   or getattr(args, "metrics_jsonl", None)
                   or getattr(args, "metrics_port", None) is not None)
    return eng.UpdatePlan(matmul=args.matmul, dispatch=args.dispatch,
                          window=args.window,
                          landmark_policy=args.landmark_policy,
                          fuse_krow=args.fuse_krow,
                          serve_every=args.serve_every,
                          serve_components=args.serve_components,
                          health=health, metrics=metrics)


def _parse_mesh(text):
    """'PtxPr' -> (P_t, P_r), e.g. '2x1'; None passes through."""
    if not text:
        return None
    pt, _, pr = text.lower().partition("x")
    return int(pt), int(pr or 1)


def _export_metrics(args, hub) -> None:
    """Flush the hub out whatever export surface the flags asked for
    (the --metrics-port HTTP server is started in main() so it scrapes
    live during the run, not just after it)."""
    if getattr(args, "metrics_jsonl", None):
        hub.close_jsonl()   # stop live streaming before the final rewrite
        obs.write_jsonl(args.metrics_jsonl, hub)
        print(f"[obs] metrics -> {args.metrics_jsonl}")


def _update_rung(args, m: int):
    """Compile key of the next update dispatch: the active bucket rung
    (bucketed dispatch recompiles per rung; fixed compiles once)."""
    from repro.core import engine as eng

    if args.dispatch != "bucketed":
        return -1
    return eng.bucket_for(max(int(m), 1), args.capacity,
                          eng.DEFAULT_PLAN.min_bucket)


class IngestServeLoop:
    """Decoupled ingest/serve over a ``StreamBatch``: ingest folds blocks
    into the working state A while query micro-batches run against the
    last PUBLISHED immutable snapshot B (``core/serving``).

    Queries for a service step are issued BEFORE that step's ingest
    dispatch — they read only the published snapshot, so they have no
    data dependency on the in-flight update and never queue behind it;
    the interleaved baseline's transform, by contrast, consumes the
    just-updated state and eats the whole update latency in its p99.
    Every ``plan.serve_every`` ingested blocks the working state is
    republished (O(M·C + M·d), never the (M, M) eigenvectors) and the
    buffer swap is a host reference flip.  ``query_fn`` overrides the
    query executor — e.g. ``distributed.make_tenant_query`` on a
    (tenant, data) 2-D mesh shards the same stacked snapshot over the
    tenant axis with zero collectives.

    **Graceful degradation** (``plan.health``): every publication is
    gated on a vmapped probe pass over the working states — an unhealthy
    tenant first gets one heal-ladder attempt (``StreamBatch.heal``); if
    the cohort still fails the verdict the publication is REFUSED
    (``skipped`` counts it) and queries keep reading the last healthy
    snapshot, so a NaN-poisoned or drifting ingest path serves
    stale-but-correct answers instead of garbage generations.

    **Staleness-aware publication** (``publish_on_drift``): instead of a
    fixed ``serve_every`` cadence, republish when any tenant's working
    top-C spectrum has drifted (relative L2) past the threshold from the
    reference frozen at the last publication — the same probe pass
    produces the verdict AND the drift, so the check costs one fused
    dispatch.  ``serve_every`` then acts as the max-staleness fallback,
    and ``drift_probe_every`` rate-limits the probe itself: the drift
    dispatch fires every k-th non-publish ingest instead of every one
    (``drift_probes`` counts the dispatches that actually ran).

    Publish/heal/drift decisions are mirrored into a ``TelemetryHub``
    (``hub=``, default the process hub) and — when the plan carries the
    metric lane — into the batch's in-graph ``MetricsState``.

    **Program spans** (``obs.span``, recorded on that hub): ``kpca.ingest``
    (id ``step``, the ingest step number, shared by its children) holds
    ``kpca.update`` and ``kpca.publish``, which holds ``kpca.probe``,
    ``kpca.heal``, ``kpca.snapshot`` and ``kpca.ref_spectrum``;
    ``kpca.query`` (id ``query``, the query sequence number) stands alone.
    """

    def __init__(self, batch, spec, *, plan=None, n_components=None,
                 query_fn=None, publish_on_drift=None,
                 drift_probe_every=1, hub=None):
        self.batch = batch
        self.spec = spec
        self.plan = plan if plan is not None else batch.plan
        self.serve_every = max(1, int(getattr(self.plan, "serve_every", 1)))
        self.n_components = n_components
        self._query_fn = query_fn
        self.policy = getattr(self.plan, "health", None)
        self.publish_on_drift = publish_on_drift
        self.drift_probe_every = max(1, int(drift_probe_every))
        self.hub = hub if hub is not None else obs.get_hub()
        self.skipped = 0           # publications refused on health
        self.heals = 0             # tenants sent down the heal ladder
        self.drift_publishes = 0   # publications triggered by drift
        self.drift_probes = 0      # drift probe dispatches actually run
        self.ref_lam = None        # (B, C) top spectrum at last publish
        self._last_drift = 0.0     # most recent probed max drift
        self._since_probe = 0
        self._steps = 0            # ingest steps begun (span id ``step``)
        self._queries = 0          # queries begun (span id ``query``)
        self.snaps = batch.publish(n_components)
        self.generation = 0          # host mirror of snaps.generation
        self._since = 0
        self._record_ref()

    def _record_ref(self):
        """Freeze the published top-C spectrum as the drift reference."""
        if self.policy is None and self.publish_on_drift is None:
            return
        from repro.core import health as hl

        st = self.batch.working_states()[0]
        nc = int(self.n_components
                 if self.n_components is not None
                 else getattr(self.plan, "serve_components", 8))
        with obs.span("kpca.ref_spectrum", hub=self.hub):
            self.ref_lam = jax.vmap(lambda s: hl.top_spectrum(s, nc))(st)
        self._last_drift = 0.0
        self._since_probe = 0

    def query(self, q):
        """(B, nq, d) queries against the published snapshot; safe to call
        at any point relative to ingest — snapshots are immutable."""
        from repro.core import serving

        self._queries += 1
        with obs.span("kpca.query", hub=self.hub, query=self._queries):
            if self._query_fn is not None:
                return self._query_fn(self.snaps, q)
            return serving.query_batch(self.snaps, q, spec=self.spec,
                                       plan=self.plan)

    def publish(self):
        """Republish the working state: new snapshot, host-flip the
        buffer.  With a health policy the publication is gated on the
        probe verdict (heal once, then refuse — the previous snapshot
        keeps serving and ``skipped`` counts the refusal).  Returns the
        current (tenant-stacked) snapshot either way."""
        with obs.span("kpca.publish", hub=self.hub):
            if self.policy is not None:
                from repro.core import health as hl

                healthy, _ = self.batch.probe_all()
                if not healthy.all():
                    try:
                        n = self.batch.heal()
                        self.heals += n
                        self.hub.inc("heals_total", n)
                    except hl.HealthError:
                        # Stored points corrupt: in-place healing impossible.
                        # Restore-from-checkpoint belongs to whoever owns the
                        # checkpoint directory — degrade to stale serving.
                        pass
                    healthy, _ = self.batch.probe_all()
                if not healthy.all():
                    self.skipped += 1
                    self.hub.inc("skipped_publishes_total")
                    self.hub.emit({"event": "skipped_publish",
                                   "generation": self.generation})
                    self.batch.note_skipped_publish()
                    return self.snaps
            self.snaps = self.batch.publish(self.n_components)
            self.generation += 1
            self.hub.inc("publishes_total")
            self.hub.set_gauge("generation", self.generation)
            self.hub.emit({"event": "publish", "generation": self.generation,
                           "drift": self._last_drift})
            self._since = 0
            self._record_ref()
            return self.snaps

    def _drift_due(self) -> bool:
        """True when any tenant's spectrum has left the published one.

        The probe dispatch is rate-limited to every ``drift_probe_every``
        call; between probes the decision rides the cached drift (which a
        publish resets), so the steady non-publish path pays the fused
        probe once per k ingests instead of every step."""
        import numpy as np

        if self.publish_on_drift is None or self.ref_lam is None:
            return False
        self._since_probe += 1
        if self._since_probe < self.drift_probe_every:
            return self._last_drift > self.publish_on_drift
        self._since_probe = 0
        self.drift_probes += 1
        self.hub.inc("drift_probes_total")
        _, drift = self.batch.probe_all(ref_lam=self.ref_lam)
        self._last_drift = float(np.max(drift))
        self.hub.set_gauge("spectral_drift", self._last_drift)
        self.batch.note_drift(drift)   # per-tenant lane gauge
        return self._last_drift > self.publish_on_drift

    def _publish_due(self) -> bool:
        """Shared publish decision (the ``ingest`` path and the timed
        decoupled driver both use it): serve_every cadence first, else
        the rate-limited drift trigger."""
        cadence = self._since >= self.serve_every
        drifted = (not cadence) and self._drift_due()
        if drifted:
            self.drift_publishes += 1
            self.hub.inc("drift_publishes_total")
        return cadence or drifted

    def ingest(self, xs) -> bool:
        """Fold one (B, d) block into the working state; republish when
        the serve_every cadence — or, with ``publish_on_drift``, the
        spectral-drift trigger — says so.  True iff a publish happened."""
        self._steps += 1
        with obs.span("kpca.ingest", hub=self.hub, step=self._steps):
            self.batch.update(xs)
            self._since += 1
            if not self._publish_due():
                return False
            gen0 = self.generation
            self.publish()
            return self.generation != gen0

    def step(self, xs, queries=None):
        """One service step: queries first (against B), then ingest
        (into A).  Returns (query results or None, published flag)."""
        y = self.query(queries) if queries is not None else None
        return y, self.ingest(xs)


def kpca_main(args) -> dict:
    import numpy as np

    from repro.core import inkpca, kernels_fn as kf

    rng = np.random.default_rng(args.seed)
    d = args.dim
    x0 = jnp.asarray(rng.normal(size=(4, d)), jnp.float32)
    spec = kf.KernelSpec(name="rbf", sigma=float(d))
    stream = inkpca.KPCAStream(x0, args.capacity, spec, adjusted=True,
                               plan=_make_plan(args), dtype=jnp.float32)

    # Ingest and query phases are timed into SEPARATE hub histograms — a
    # single flattened latency list conflated update steps with transform
    # calls, and warm-up compiles (first call per bucket rung / component
    # count) polluted the percentiles.  Keyed first calls go to
    # *_compile_ms (obs.LatencyHistogram).
    hub = obs.fresh_hub()
    upd, qry = hub.histogram("update_ms"), hub.histogram("query_ms")
    n_served = 0
    n_heals = 0
    t_total = time.time()
    for i in range(args.points):
        x = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
        rung = _update_rung(args, int(stream.kpca_state.m) + 1)
        with upd.timed(key=rung) as t:
            stream.update(x)
            st = stream.kpca_state
            t.sync(st.L)
        if (i + 1) % args.transform_every == 0:
            # Self-healing cadence rides the transform interval: one host
            # read of the in-graph probe verdict, heal ladder on failure.
            if args.health and not stream.is_healthy():
                stream.heal()
                n_heals += 1
                hub.inc("heals_total")
                st = stream.kpca_state
            q = jnp.asarray(rng.normal(size=(args.batch, d)), jnp.float32)
            n_comp = min(8, int(st.m))
            with qry.timed(key=n_comp) as t:
                t.sync(stream.transform(q, n_components=n_comp))
            n_served += args.batch
    t_total = time.time() - t_total

    st = stream.kpca_state
    result = {
        "mode": "kpca", "dispatch": args.dispatch, "capacity": args.capacity,
        "window": args.window,
        "points": args.points, "m_final": int(st.m),
        **upd.summary("update_ms"),
        **qry.summary("query_ms"),
        "transforms_served": n_served,
        "total_s": t_total,
        "finite": bool(jnp.isfinite(st.L).all()),
    }
    if args.health:
        result["heals"] = n_heals
        result["health"] = stream.health_report()
    if stream.metrics is not None:
        result["metrics"] = hub.observe_metrics_state(stream.metrics)
    _export_metrics(args, hub)
    print(f"[serve/kpca] {args.dispatch}: {args.points} updates to "
          f"m={result['m_final']} (capacity {args.capacity}, "
          f"window {args.window}), "
          f"update p50 {result['update_ms_p50']:.1f} ms, "
          f"query p50 {result['query_ms_p50']:.1f} ms  {result}")
    return result


def nystrom_lifecycle(engine, state, xs, *, budget: int, rule, hub,
                      quarantine: bool = False) -> dict:
    """Stream the points ``xs`` through the Nyström landmark lifecycle:
    each point becomes an observed row, then a landmark candidate under
    ``engine.plan.landmark_policy`` until ``rule`` (a
    ``nystrom.SufficientSubsetRule``) declares the subset sufficient.

    With the leverage policy a ``nystrom.TraceErrorTracker`` keeps
    ``trace_error`` current from O(n·m) increments; it freezes with the
    rule, so ``tracker_rows`` counts the rows it has seen.  Returns the
    final state, the tracker, ``stopped_at`` and the quarantine count;
    admissions are counted in ``hub`` (``landmark_total{action}``)."""
    from repro.core import nystrom

    admit = {k: hub.counter("landmark_total", action=k)
             for k in ("admitted", "rejected", "replaced")}
    ms = None
    if engine.plan.metrics:
        from repro.core import telemetry as tm

        ms = tm.init_metrics()
    n_quarantined = 0
    stopped_at = None
    leverage = engine.plan.landmark_policy == "leverage"
    # Incremental trace_error: O(n·m) per admission instead of the
    # O(n·m²) exact recompute the stopping rule used to trigger.
    tracker = nystrom.TraceErrorTracker(state, engine.spec) if leverage \
        else None
    tracker_rows = int(state.Knm.shape[0])
    for i, x in enumerate(xs):
        if leverage and rule.sufficient:
            # Admissions have stopped: the rest of the points only become
            # observed rows, appended in ONE observe_rows call — each row
            # count is a new Knm shape, so appending row by row would
            # compile a new concatenation for every point.
            rest = xs[i:]
            bad = 0
            if quarantine:
                import numpy as np

                bad = int((~np.isfinite(np.asarray(rest)).all(axis=1)).sum())
                n_quarantined += bad
                hub.inc("quarantined_total", bad)
            state = nystrom.observe_rows(state, rest, engine.spec,
                                         plan=engine.plan)
            admit["rejected"].inc(rest.shape[0] - bad)
            break
        if quarantine and not bool(jnp.isfinite(x).all()):
            # The observe_rows gate would drop the row anyway; counting
            # and skipping here keeps it out of the landmark offer too.
            n_quarantined += 1
            hub.inc("quarantined_total")
            continue
        res = None
        if leverage:
            # ONE residual dispatch serves both the tracker's observe
            # increment and the admission gate below.  Once the rule has
            # stopped admissions the tracker freezes too (the branch at
            # the top of the loop).
            res = float(nystrom.admission_residual(state, x, engine.spec))
            tracker.observe(state, x, residual=res)
            tracker_rows += 1
        state = nystrom.observe_rows(state, x, engine.spec, plan=engine.plan)
        prev = state
        state, action = engine.offer_landmark(state, x, budget=budget,
                                              residual=res)
        admit[action].inc()
        if leverage and action in ("admitted", "replaced"):
            if action == "admitted":
                tracker.admitted(prev, x)
            else:
                # Incremental leave-one-out swap delta: no exact resync
                # unless the delta itself is numerically untrustworthy.
                tracker.replaced(state, state_before=prev, x=x)
            tracker.maybe_resync(state)
            if ms is not None:
                from repro.core import telemetry as tm

                ms = tm.note_trace_error(ms, tracker.value)
            if rule.observe(tracker.value):
                stopped_at = i
    if ms is not None:
        hub.observe_metrics_state(ms, prefix="nystrom")
    return {"state": state, "tracker": tracker, "tracker_rows": tracker_rows,
            "stopped_at": stopped_at, "quarantined": n_quarantined,
            "counts": {k: int(c.value) for k, c in admit.items()}}


def nystrom_main(args) -> dict:
    """Streaming Nyström landmark-lifecycle service (grow_rows mode)."""
    import numpy as np

    from repro.core import engine as eng, kernels_fn as kf, nystrom

    rng = np.random.default_rng(args.seed)
    d = args.dim
    spec = kf.KernelSpec(name="rbf", sigma=float(d))
    engine = eng.Engine(spec, _make_plan(args), adjusted=False)
    x0 = jnp.asarray(rng.normal(size=(4, d)), jnp.float32)
    state = nystrom.init_nystrom(None, x0, args.capacity, spec,
                                 grow_rows=True)
    rule = nystrom.SufficientSubsetRule(rel_tol=args.stop_rel_tol,
                                        patience=args.stop_patience)
    budget = args.landmark_budget or args.capacity - 1
    hub = obs.fresh_hub()
    quarantine = (getattr(engine.plan, "health", None) is not None
                  and engine.plan.health.quarantine)
    xs = jnp.asarray(rng.normal(size=(args.points, d)), jnp.float32)
    t_total = time.time()
    run = nystrom_lifecycle(engine, state, xs, budget=budget, rule=rule,
                            hub=hub, quarantine=quarantine)
    t_total = time.time() - t_total
    state, tracker, counts = run["state"], run["tracker"], run["counts"]

    err = float(nystrom.trace_error(state, spec))
    hub.set_gauge("trace_error", err)
    hub.set_gauge("active_m", int(state.kpca.m))
    result = {
        "mode": "nystrom", "policy": args.landmark_policy,
        "capacity": args.capacity, "budget": budget,
        "points": args.points, "m_final": int(state.kpca.m),
        "rows": int(state.Knm.shape[0]),
        "trace_error": err, "stopped_at": run["stopped_at"],
        # Drift is only meaningful while the tracker was live: after the
        # stopping rule fires it freezes (rows keep arriving untracked).
        "tracker_drift": (abs(tracker.value - err)
                          if tracker and not rule.sufficient else None),
        "total_s": t_total,
        "finite": bool(jnp.isfinite(state.kpca.L).all()
                       and np.isfinite(err)),
        **counts,
    }
    if quarantine:
        result["quarantined"] = run["quarantined"]
    _export_metrics(args, hub)
    print(f"[serve/nystrom] {args.landmark_policy}: {args.points} points, "
          f"{counts['admitted']} admitted / {counts['replaced']} replaced / "
          f"{counts['rejected']} rejected -> m={result['m_final']}, "
          f"trace err {err:.4f}, stopped_at={run['stopped_at']}  {result}")
    return result


def kpca_multitenant_main(args) -> dict:
    """B independent tenant streams, one vmapped device step per point."""
    import numpy as np

    from repro.core import engine as eng, kernels_fn as kf

    rng = np.random.default_rng(args.seed)
    B, d = args.tenants, args.dim
    spec = kf.KernelSpec(name="rbf", sigma=float(d))
    x0 = jnp.asarray(rng.normal(size=(B, 4, d)), jnp.float32)
    batch = eng.StreamBatch(x0, args.capacity, spec, plan=_make_plan(args),
                            adjusted=True, dtype=jnp.float32,
                            cohorts=args.cohorts, window=args.window)

    # Ingest steps and transform calls are timed into separate hub
    # histograms (they used to share one flattened list — and transforms
    # were never timed at all), with warm-up compiles split out per
    # rung-set / component count.
    hub = obs.fresh_hub()
    upd, qry = hub.histogram("step_ms"), hub.histogram("query_ms")
    n_served = 0
    t_total = time.time()
    for i in range(args.points):
        xs = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
        rungs = tuple(sorted({_update_rung(args, int(v) + 1)
                              for st in batch.working_states()
                              for v in np.atleast_1d(st.m)}))
        with upd.timed(key=rungs) as t:
            batch.update(xs)
            t.sync([st.L for st in batch.working_states()])
        if (i + 1) % args.transform_every == 0:
            q = jnp.asarray(rng.normal(size=(B, args.batch, d)), jnp.float32)
            n_comp = min(8, min(int(v) for st in batch.working_states()
                                for v in np.atleast_1d(st.m)))
            with qry.timed(key=n_comp) as t:
                t.sync(batch.transform(q, n_components=n_comp))
            n_served += B * args.batch
    t_total = time.time() - t_total

    m_final = [int(v) for v in np.asarray(batch.states.m)]
    steady = np.median(np.asarray(upd.ms)) if upd.ms else float("nan")
    result = {
        "mode": "kpca-multitenant", "tenants": B,
        "dispatch": args.dispatch, "cohorts": args.cohorts,
        "window": args.window,
        "capacity": args.capacity,
        "points": args.points, "m_final": m_final,
        **upd.summary("step_ms"),
        **qry.summary("query_ms"),
        "aggregate_updates_per_s": float(B / (steady / 1e3)),
        "transforms_served": n_served,
        "total_s": t_total,
        "finite": bool(jnp.isfinite(batch.states.L).all()),
    }
    if args.health:
        result["quarantined"] = batch.health_summary()["quarantined"]
    if batch.metrics is not None:
        report = hub.observe_metrics_state(batch.metrics)
        result["metrics"] = {k: (v.tolist() if hasattr(v, "tolist") else v)
                             for k, v in report.items()}
    _export_metrics(args, hub)
    print(f"[serve/kpca] {B} tenants x {args.points} updates to "
          f"m={m_final[0]} (capacity {args.capacity}), "
          f"step p50 {result['step_ms_p50']:.1f} ms = "
          f"{result['aggregate_updates_per_s']:.0f} updates/s aggregate, "
          f"query p50 {result['query_ms_p50']:.1f} ms  {result}")
    return result


def kpca_decoupled_main(args) -> dict:
    """Decoupled ingest/serve (``--decouple``): B tenant streams ingest
    into working state A while ``--query-rate`` query micro-batches per
    step run against the published snapshot B — the ``IngestServeLoop``.

    With ``--mesh PtxPr`` the query path runs tenant-sharded over a
    (tenant, data) 2-D mesh (``distributed.make_tenant_query``) when the
    host exposes P_t x P_r devices (XLA_FLAGS=--xla_force_host_platform_-
    device_count=N on CPU).  Reported query percentiles are measured
    UNDER concurrent ingest; publish (snapshot swap) cost is timed
    separately — see benchmarks/bench_serving.py for the controlled
    comparison against the interleaved baseline.
    """
    import numpy as np

    from repro.core import engine as eng, kernels_fn as kf

    rng = np.random.default_rng(args.seed)
    B, d = args.tenants, args.dim
    plan = _make_plan(args)
    spec = kf.KernelSpec(name="rbf", sigma=float(d))
    x0 = jnp.asarray(rng.normal(size=(B, 4, d)), jnp.float32)
    batch = eng.StreamBatch(x0, args.capacity, spec, plan=plan,
                            adjusted=True, dtype=jnp.float32,
                            cohorts=args.cohorts, window=args.window)

    query_fn = None
    mesh_shape = _parse_mesh(args.mesh)
    if mesh_shape is not None:
        from repro.core import distributed as dist

        pt, pr = mesh_shape
        if len(jax.devices()) >= pt * pr and B % pt == 0:
            tmesh = dist.make_tenant_mesh(pt, pr)
            query_fn = dist.make_tenant_query(tmesh, spec, plan=plan)
        else:
            print(f"[serve/kpca-decoupled] --mesh {args.mesh} needs "
                  f"{pt * pr} devices (have {len(jax.devices())}) and "
                  f"P_t | tenants; falling back to local queries")

    hub = obs.fresh_hub()
    loop = IngestServeLoop(batch, spec, plan=plan, query_fn=query_fn,
                           publish_on_drift=args.publish_on_drift,
                           drift_probe_every=args.drift_probe_every,
                           hub=hub)
    ing, qry, pub = (hub.histogram("ingest_ms"), hub.histogram("query_ms"),
                     hub.histogram("publish_ms"))
    n_served = 0
    t_total = time.time()
    for i in range(args.points):
        xs = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
        # Queries first: they read only the published snapshot, so they
        # never wait on this step's ingest.
        for _ in range(args.query_rate):
            q = jnp.asarray(rng.normal(size=(B, args.batch, d)), jnp.float32)
            with qry.timed(key=loop.generation == 0) as t:
                t.sync(loop.query(q))
            n_served += B * args.batch
        rungs = tuple(sorted({_update_rung(args, int(v) + 1)
                              for st in batch.working_states()
                              for v in np.atleast_1d(st.m)}))
        with ing.timed(key=rungs) as t:
            batch.update(xs)
            t.sync([st.L for st in batch.working_states()])
        loop._since += 1
        if loop._publish_due():
            with pub.timed(key=rungs) as t:
                t.sync(loop.publish().S)
    t_total = time.time() - t_total

    m_final = [int(v) for v in np.asarray(batch.states.m)]
    result = {
        "mode": "kpca-decoupled", "tenants": B,
        "dispatch": args.dispatch, "cohorts": args.cohorts,
        "capacity": args.capacity, "window": args.window,
        "mesh": args.mesh, "tenant_sharded_queries": query_fn is not None,
        "serve_every": args.serve_every,
        "query_rate": args.query_rate,
        "publish_on_drift": args.publish_on_drift,
        "points": args.points, "m_final": m_final,
        "generations": loop.generation,
        "drift_publishes": loop.drift_publishes,
        "drift_probes": loop.drift_probes,
        "skipped_publishes": loop.skipped,
        "heals": loop.heals,
        "quarantined": int(batch.quarantined.sum()),
        **ing.summary("ingest_ms"),
        **qry.summary("query_ms"),
        **pub.summary("publish_ms"),
        "queries_served": n_served,
        "total_s": t_total,
        "finite": bool(jnp.isfinite(batch.states.L).all()),
    }
    if batch.metrics is not None:
        report = hub.observe_metrics_state(batch.metrics)
        result["metrics"] = {k: (v.tolist() if hasattr(v, "tolist") else v)
                             for k, v in report.items()}
    _export_metrics(args, hub)
    print(f"[serve/kpca-decoupled] {B} tenants x {args.points} blocks "
          f"(publish every {args.serve_every}), "
          f"ingest p50 {result['ingest_ms_p50']:.1f} ms, "
          f"query p50 {result['query_ms_p50']:.2f} / "
          f"p99 {result['query_ms_p99']:.2f} ms under ingest, "
          f"publish p50 {result['publish_ms_p50']:.2f} ms  {result}")
    return result


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("lm", "kpca", "nystrom"),
                    default="lm")
    ap.add_argument("--arch", default="qwen3_32b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    # kpca-mode flags
    ap.add_argument("--capacity", type=int, default=512)
    ap.add_argument("--points", type=int, default=100)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--dispatch", choices=("fixed", "bucketed"),
                    default="bucketed")
    ap.add_argument("--matmul", default="jnp",
                    choices=("jnp", "pallas", "jnp2", "pallas2"))
    ap.add_argument("--transform-every", type=int, default=16)
    ap.add_argument("--fuse-krow", action="store_true",
                    help="route ingest + batched transform through the "
                         "fused kernel-row producers (single dispatch "
                         "builds the kernel row and projects it; see "
                         "kernels/rbf_gram/krow_fused.py)")
    ap.add_argument("--tenants", type=int, default=1,
                    help="number of independent KPCA streams folded per "
                         "vmapped device step (kpca mode)")
    ap.add_argument("--cohorts", choices=("max", "bucket", "bucket-padded"),
                    default="max",
                    help="multi-tenant cohort geometry: 'max' runs the "
                         "whole cohort at the largest tenant's bucket; "
                         "'bucket' groups tenants by their own bucket; "
                         "'bucket-padded' additionally pads group sizes "
                         "to powers of two (bounded recompiles under "
                         "tenant churn)")
    ap.add_argument("--window", type=int, default=None,
                    help="sliding-window size W: evict the oldest point "
                         "before ingesting past a full window (kpca mode, "
                         "single and multi-tenant)")
    ap.add_argument("--decouple", action="store_true",
                    help="decoupled ingest/serve: queries run against the "
                         "last published immutable snapshot instead of "
                         "the working state (kpca mode, any tenant count)")
    ap.add_argument("--query-rate", type=int, default=1,
                    help="decoupled mode: query micro-batches (of --batch "
                         "points each, per tenant) issued per ingest step "
                         "against the published snapshot")
    ap.add_argument("--serve-every", type=int, default=1,
                    help="decoupled mode: republish the serving snapshot "
                         "every N ingested blocks")
    ap.add_argument("--serve-components", type=int, default=8,
                    help="components C frozen into published snapshots")
    ap.add_argument("--health", action="store_true",
                    help="attach the default health policy to the plan: "
                         "in-graph probes ride the update, non-finite "
                         "points are quarantined before the rank-one "
                         "pair fires, and unhealthy states go down the "
                         "heal ladder instead of being served")
    ap.add_argument("--metrics", action="store_true",
                    help="attach the in-graph metric lane (MetricsState) "
                         "to the plan: per-stream counters and gauges "
                         "ride the update pytree with zero extra host "
                         "syncs; implied by the export flags below")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve GET /metrics (Prometheus text format, "
                         "counters + gauges + phase-latency summaries) "
                         "from a daemon thread during the run; 0 picks "
                         "an ephemeral port")
    ap.add_argument("--metrics-jsonl", default=None, metavar="PATH",
                    help="append hub events to PATH during the run; at "
                         "exit rewrite it with what the hub's rings hold "
                         "(the last events, then the last program spans) "
                         "and a final full-registry scrape line (one JSON "
                         "object per line)")
    ap.add_argument("--drift-probe-every", type=int, default=4,
                    metavar="K",
                    help="decoupled mode: run the spectral-drift probe "
                         "dispatch every K-th non-publish ingest instead "
                         "of every one (--publish-on-drift)")
    ap.add_argument("--publish-on-drift", type=float, default=None,
                    metavar="THRESH",
                    help="decoupled mode: staleness-aware publication — "
                         "republish when any tenant's working top-C "
                         "spectrum drifts (relative L2) past THRESH from "
                         "the last published reference; --serve-every "
                         "then acts as the max-staleness fallback")
    ap.add_argument("--mesh", default=None, metavar="PtxPr",
                    help="decoupled mode: 2-D (tenant, data) mesh shape, "
                         "e.g. '2x1' — tenant-shards the query path over "
                         "P_t x P_r devices when the host exposes them")
    ap.add_argument("--landmark-policy", choices=("append", "leverage"),
                    default="append",
                    help="nystrom mode admission policy (see module "
                         "docstring)")
    ap.add_argument("--landmark-budget", type=int, default=None,
                    help="max landmarks (default capacity - 1)")
    ap.add_argument("--stop-rel-tol", type=float, default=1e-2,
                    help="sufficient-subset rule: relative improvement "
                         "below this counts as flat")
    ap.add_argument("--stop-patience", type=int, default=3,
                    help="sufficient-subset rule: consecutive flat "
                         "admissions before stopping")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.metrics_port is not None:
        # Start before the mode main so the run is scrapeable live; the
        # mains reset the same default-hub OBJECT (fresh_hub), so the
        # server keeps reading the active registry.  Daemon thread —
        # dies with the process.
        srv = obs.serve_metrics(obs.get_hub(), args.metrics_port)
        print(f"[obs] /metrics on :{srv.server_address[1]}")
    if args.metrics_jsonl:
        obs.get_hub().open_jsonl(args.metrics_jsonl)

    if args.mode == "nystrom":
        return nystrom_main(args)
    if args.mode == "kpca":
        if args.decouple:
            return kpca_decoupled_main(args)
        if args.tenants > 1:
            return kpca_multitenant_main(args)
        return kpca_main(args)

    cfg = configs.get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh()
    max_seq = args.prompt_len + args.gen

    with shd.use_mesh(mesh):
        params = lm.init_params(jax.random.PRNGKey(args.seed), cfg)
        serve_step = jax.jit(steps_lib.make_serve_step(cfg))

        stream = TokenStream(vocab=cfg.vocab, seq_len=args.prompt_len,
                             global_batch=args.batch, seed=args.seed)
        prompts = stream.batch_at(jnp.int32(0))["tokens"]

        caches = lm.init_caches(params, cfg, args.batch, max_seq)
        # Prefill: teacher-forced decode over the prompt (cache warm-up).
        t0 = time.time()
        tok = prompts[:, :1]
        for t in range(args.prompt_len):
            pos = jnp.full((args.batch, 1), t, jnp.int32)
            nxt, _, caches = serve_step(params, caches, prompts[:, t:t+1],
                                        pos)
        t_prefill = time.time() - t0

        # Decode: greedy continuation.
        generated = []
        tok = nxt
        t0 = time.time()
        for t in range(args.prompt_len, max_seq):
            pos = jnp.full((args.batch, 1), t, jnp.int32)
            tok, _, caches = serve_step(params, caches, tok, pos)
            generated.append(tok)
        t_decode = time.time() - t0

    gen = jnp.concatenate(generated, axis=1)
    toks_per_s = args.batch * args.gen / max(t_decode, 1e-9)
    result = {"prefill_s": t_prefill, "decode_s": t_decode,
              "tokens_per_s": toks_per_s,
              "generated_shape": tuple(gen.shape),
              "finite": bool(jnp.isfinite(gen).all())}
    print(f"served {args.batch}x{args.gen} tokens: "
          f"{toks_per_s:.1f} tok/s (CPU smoke) {result}")
    return result


if __name__ == "__main__":
    main()
