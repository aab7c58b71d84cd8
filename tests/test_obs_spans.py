"""Program spans and the JAX trace/compile counters booked to them
(``repro.obs.trace``), and the stage names on the update's device
programs.

* One ``IngestServeLoop`` step records the span tree of the service's
  layers, its spans sharing one step id; a query has its own id.
* The hub's event and span rings stay bounded.
* An eager ``vmap`` books its traces to the span it ran in; a warm
  jitted call books none.
* Recording spans never changes the eigensystem or the snapshot.
* ``kpca.krow``/``solve``/``rotate``/``downdate`` name the stages in the
  ``op_name`` metadata of the lowered update and downdate programs.
"""
import collections
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import engine as eng
from repro.core import health as hl
from repro.core import kernels_fn as kf
from repro.obs import hub as obs_hub

SPEC = kf.KernelSpec(name="rbf", sigma=2.0)
B, W, D = 2, 8, 3


def _loop(hub, seed=0):
    from repro.launch.serve import IngestServeLoop

    rng = np.random.default_rng(seed)
    plan = eng.UpdatePlan(window=W, health=hl.DEFAULT_POLICY,
                          serve_components=4)
    batch = eng.StreamBatch(jnp.asarray(rng.normal(size=(B, W, D))), 16,
                            SPEC, plan=plan, adjusted=True,
                            dtype=jnp.float32, window=W)
    loop = IngestServeLoop(batch, SPEC, plan=plan, n_components=4, hub=hub)
    return loop, rng


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        bool(jnp.array_equal(x, y, equal_nan=True)) for x, y in zip(la, lb))


def test_service_step_records_the_span_tree():
    hub = obs.TelemetryHub()
    loop, rng = _loop(hub)
    hub.spans.clear()                 # set-up's own publish and reference
    loop.ingest(rng.normal(size=(B, D)).astype(np.float32))
    loop.query(rng.normal(size=(B, 5, D)).astype(np.float32))
    recs = list(hub.spans)
    parents = {r["name"]: r["parent"] for r in recs}
    assert parents == {
        "kpca.update": "kpca.ingest", "kpca.probe": "kpca.publish",
        "kpca.snapshot": "kpca.publish", "kpca.ref_spectrum": "kpca.publish",
        "kpca.publish": "kpca.ingest", "kpca.ingest": None,
        "kpca.query": None}
    step = [r for r in recs if r["name"] != "kpca.query"]
    assert {r["step"] for r in step} == {1}
    assert all("query" not in r for r in step)
    (query,) = [r for r in recs if r["name"] == "kpca.query"]
    assert query["query"] == 1 and "step" not in query
    # children close inside their parent, on the same clock
    by = {r["name"]: r for r in recs}
    for r in step:
        if r["parent"] is not None:
            p = by[r["parent"]]
            assert p["start_ns"] <= r["start_ns"] <= r["end_ns"] <= p["end_ns"]
    assert by["kpca.ingest"]["end_ns"] <= query["start_ns"]
    # a second step and query carry the next ids
    loop.ingest(rng.normal(size=(B, D)).astype(np.float32))
    loop.query(rng.normal(size=(B, 5, D)).astype(np.float32))
    assert {r.get("step") for r in list(hub.spans)[len(recs):]} == {2, None}
    assert list(hub.spans)[-1]["query"] == 2


def test_span_ids_inherit_and_a_raising_span_still_records():
    hub = obs.TelemetryHub()
    with pytest.raises(ValueError):
        with obs.span("outer", hub=hub, step=7):
            with obs.span("inner", query=3):
                raise ValueError("boom")
    inner, outer = list(hub.spans)
    assert (inner["name"], inner["parent"]) == ("inner", "outer")
    assert (inner["step"], inner["query"]) == (7, 3)
    assert (outer["parent"], outer["step"]) == (None, 7)
    assert obs.trace.current() is None


@pytest.mark.parametrize("ring,maxlen", [("spans", obs_hub.SPAN_RING),
                                         ("events", obs_hub.EVENT_RING)])
def test_rings_stay_bounded(ring, maxlen):
    hub = obs.TelemetryHub()
    for i in range(maxlen + 10):
        if ring == "spans":
            with obs.span("s", hub=hub, step=i):
                pass
        else:
            hub.emit({"event": "publish", "generation": i})
    kept = getattr(hub, ring)
    assert len(kept) == maxlen
    key = "step" if ring == "spans" else "generation"
    assert kept[0][key] == 10 and kept[-1][key] == maxlen + 9


def test_eager_vmap_books_traces_to_its_span():
    hub = obs.TelemetryHub()
    x = jnp.ones((3, 4, 4))
    f = lambda r: jnp.tanh(r @ r.T).sum()                  # noqa: E731
    jax.vmap(f)(x)                    # compile each primitive once first
    with obs.span("t.eager", hub=hub):
        jax.vmap(f)(x)
    sc = hub.scrape()
    assert sc['jax_traces_total{span="t.eager"}'] >= 3
    assert sc['jax_trace_seconds_total{span="t.eager"}'] > 0.0
    (rec,) = list(hub.spans)
    assert rec["traces"] == sc['jax_traces_total{span="t.eager"}']
    assert rec["trace_s"] == pytest.approx(
        sc['jax_trace_seconds_total{span="t.eager"}'])
    assert 'jax_traces_total{span="none"}' not in sc


def test_warm_jit_books_no_trace_and_a_cold_one_books_its_compile():
    hub = obs.TelemetryHub()
    g = jax.jit(lambda r: jnp.sin(r) * 3.0)
    x = jnp.ones((5,))
    with obs.span("t.cold", hub=hub):
        g(x)
    with obs.span("t.warm", hub=hub):
        g(x)
    sc = hub.scrape()
    assert sc['jax_traces_total{span="t.cold"}'] >= 1
    assert sc['jax_compiles_total{span="t.cold"}'] >= 1
    assert not any('span="t.warm"' in k for k in sc)
    cold, warm = list(hub.spans)
    assert (warm["traces"], warm["compiles"]) == (0, 0)
    assert cold["compiles"] == sc['jax_compiles_total{span="t.cold"}']


def test_span_counters_reach_prometheus_and_jsonl(tmp_path):
    hub = obs.TelemetryHub()
    with obs.span("t.phase", hub=hub, step=1):
        jax.vmap(lambda r: r * 2.0)(jnp.ones((2, 3)))
    hub.emit({"event": "publish", "generation": 1})
    parsed = obs.parse_prometheus(hub.to_prometheus())
    assert parsed['jax_traces_total{span="t.phase"}'] >= 1
    path = tmp_path / "m.jsonl"
    obs.write_jsonl(path, hub)
    lines = obs.read_jsonl(path)
    assert [e["event"] for e in lines] == ["publish", "span", "scrape"]
    assert lines[1]["name"] == "t.phase" and lines[1]["step"] == 1
    assert lines[2]['jax_traces_total{span="t.phase"}'] >= 1


def test_spans_leave_the_step_bitwise_unchanged():
    outs = []
    for keep in (True, False):
        hub = obs.TelemetryHub()
        if not keep:
            hub.spans = collections.deque(maxlen=0)
        loop, rng = _loop(hub, seed=3)
        for _ in range(3):
            loop.ingest(rng.normal(size=(B, D)).astype(np.float32))
        assert (len(hub.spans) > 0) is keep
        outs.append((loop.batch.working_states(), loop.snaps))
    assert _leaves_equal(outs[0][0], outs[1][0])
    assert _leaves_equal(outs[0][1], outs[1][1])


def _innermost_stages(hlo_text: str) -> set:
    stages = ("kpca.krow", "kpca.solve", "kpca.rotate", "kpca.downdate")
    found = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo_text):
        hits = [(name.rfind(s), s) for s in stages if s in name]
        if hits:
            found.add(max(hits)[1])
    return found


@pytest.mark.parametrize("program,stages", [
    ("update", {"kpca.krow", "kpca.solve", "kpca.rotate"}),
    ("downdate", {"kpca.krow", "kpca.solve", "kpca.rotate",
                  "kpca.downdate"})])
def test_stage_scopes_name_the_update_programs(program, stages):
    rng = np.random.default_rng(1)
    plan = eng.UpdatePlan(window=W, health=hl.DEFAULT_POLICY)
    batch = eng.StreamBatch(jnp.asarray(rng.normal(size=(B, W, D))), 16,
                            SPEC, plan=plan, adjusted=True,
                            dtype=jnp.float32, window=W)
    st = batch.working_states()[0]
    kp = plan.kernel_plan()
    if program == "update":
        xs = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)
        low = eng._batched_update.lower(st, xs, SPEC, True, kp)
    else:
        low = eng._batched_downdate_masked.lower(
            st, jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool), SPEC,
            True, kp)
    assert _innermost_stages(low.compile().as_text()) == stages
