"""The readers of the program's own spans and counters, the stage names
of the update programs, and ``tools/program_trace.py`` on a tiny cell
run on the CPU."""
import importlib.util
import io
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, TINY_TRAFFIC, tiny_config

import run as bench_run
from harness import program
from harness import trace as ht
from harness.trace import Op, Span, Trace


def _tool():
    spec = importlib.util.spec_from_file_location(
        "program_trace", BENCH / "tools" / "program_trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Run:
    def __init__(self, steps, queries):
        self.steps, self.queries = steps, queries


def _rec(name, start_ms, end_ms, parent=None, trace_s=0.0, **ids):
    return {"name": name, "start_ns": int(start_ms * 1e6),
            "end_ns": int(end_ms * 1e6), "parent": parent, **ids,
            "traces": 0, "trace_s": trace_s, "compiles": 0}


def _step(k, t0, probe_ms, publish_ms, trace_s=0.001):
    """One step's records, children closing before their parents."""
    pub0 = t0 + 5
    return [_rec("kpca.update", t0, t0 + 5, "kpca.ingest", step=k),
            _rec("kpca.probe", pub0, pub0 + probe_ms, "kpca.publish",
                 trace_s=trace_s, step=k),
            _rec("kpca.snapshot", pub0 + probe_ms, pub0 + probe_ms + 1,
                 "kpca.publish", trace_s=trace_s, step=k),
            _rec("kpca.publish", pub0, pub0 + publish_ms, "kpca.ingest",
                 step=k),
            _rec("kpca.ingest", t0, pub0 + publish_ms, step=k)]


def _ring():
    # an earlier loop, then this loop's warm-up step 1 and query 1, then
    # the window: steps 2-3 and queries 2-4
    recs = _step(9, 0, 50, 60) + _step(1, 100, 50, 60)
    recs.append(_rec("kpca.query", 170, 190, trace_s=0.5, query=1))
    recs += _step(2, 200, 100, 110) + _step(3, 400, 120, 150)
    for q, (s, e) in enumerate([(320, 340), (350, 380), (600, 610)], 2):
        recs.append(_rec("kpca.query", s, e, trace_s=0.002, query=q))
    return recs


@pytest.fixture
def ring(monkeypatch):
    monkeypatch.setattr(program, "ring", _ring)


def test_window_records_are_the_trailing_requests():
    recs = _ring()
    steps = program.window_records(_Run(2, 3), "step", recs)
    assert sorted({r["step"] for r in steps}) == [2, 3]
    assert len(steps) == 10
    queries = program.window_records(_Run(2, 3), "query", recs)
    assert [r["query"] for r in queries] == [2, 3, 4]
    assert program.window_records(_Run(0, 3), "step", recs) is None
    assert program.window_records(_Run(2, 3), "step", []) is None


@pytest.mark.parametrize("name,value", [
    ("query_host_ms", 20.0),                   # median of 20, 30, 10
    ("query_trace_ms", 1e3 * 0.006 / 3),
    ("step_trace_ms", 1e3 * 0.004 / 2),        # probe + snapshot, 2 steps
    ("publish_host_ms", (10.0 + 30.0) / 2),    # publish minus its probe
])
def test_program_readers(ring, name, value):
    got = bench_run.load_reader(name)(_Run(2, 3))
    assert got == pytest.approx(value)


@pytest.mark.parametrize("name", ["query_host_ms", "query_trace_ms",
                                  "step_trace_ms", "publish_host_ms"])
def test_program_readers_are_silent_without_a_ring(monkeypatch, name):
    monkeypatch.setattr(program, "ring", lambda: None)
    assert bench_run.load_reader(name)(_Run(2, 3)) is None


@pytest.mark.parametrize("path,stage", [
    ("jit(_batched_update)/vmap(jit(update_adjusted))/jit(rank_one_update)"
     "/kpca.solve/while/body/div", "kpca.solve"),
    ("jit(_batched_downdate_masked)/vmap(jit(downdate))/kpca.downdate"
     "/gather", "kpca.downdate"),
    ("jit(_batched_downdate_masked)/vmap(jit(downdate))/jit(downdate_"
     "adjusted)/jit(rank_one_update)/kpca.rotate/dot_general", "kpca.rotate"),
    ("jit(f)/vmap(kpca.krow)/dot_general", "kpca.krow"),
    ("jit(_batched_update)/vmap(jit(update_adjusted))/add", "unscoped"),
])
def test_stage_is_the_innermost_scope(path, stage):
    assert program.stage_of(path) == stage


def test_hlo_stages_by_instruction_name():
    text = "\n".join([
        '  %fusion.184 = f32[8,2048,2048]{1,2,0} fusion(%a), kind=kOutput, '
        'calls=%fc, metadata={op_name="jit(f)/jit(rank_one_update)/'
        'kpca.rotate/dot_general" stack_frame_id=2}',
        '  ROOT %while.3 = (s32[]) while(%t), condition=%c, body=%b, '
        'metadata={op_name="jit(f)/kpca.solve/while"}',
        '  %copy.1 = f32[8]{0} copy(%x), metadata={op_name="jit(f)/add"}',
        '  %param.0 = f32[8]{0} parameter(0)'])
    assert program.hlo_stages(text) == {"fusion.184": "kpca.rotate",
                                        "while.3": "kpca.solve",
                                        "copy.1": "unscoped"}
    assert program.instruction(
        "%fusion.184 = f32[8,2048,2048]{1,2,0:T(8,128)} fusion(...)") \
        == "fusion.184"
    assert program.instruction("dot_general.1") == "dot_general.1"


def _trace():
    dev = "/device:TPU:0"
    tr = Trace()
    tr.modules = [Op(100, 400, "jit__batched_update(1)",
                     "jit__batched_update(1)", "", dev)]
    tr.ops = [Op(100, 200, "%fusion.1 = f32[8] fusion()", "", "", dev),
              Op(250, 300, "%while.2 = (s32[]) while()", "", "", dev),
              Op(320, 400, "%copy.3 = f32[8] copy()", "", "", dev)]
    tr.spans = [Span(0, 1000, "bench.window"),
                Span(50, 450, "bench.ingest"),
                Span(60, 440, "bench.publish"),
                Span(450, 590, "bench.wait")]
    ht.attribute(tr)
    return tr


def test_kpca_spans_label_the_gaps_and_leave_the_readers_alone():
    tool = _tool()
    tr = _trace()
    before = [(o.module, o.span) for o in tr.ops]
    red = ht.Reduced(tr, 0, 1000)
    idle0 = (red.idle_pct(), red.idle_gaps(10))
    kpca = [Span(61, 439, "kpca.publish"), Span(62, 240, "kpca.probe"),
            Span(240, 310, "kpca.snapshot")]
    gaps = tool.labelled_gaps(red, kpca)
    # the harness's own reduction never sees the program's spans
    assert [(o.module, o.span) for o in tr.ops] == before
    assert (red.idle_pct(), red.idle_gaps(10)) == idle0
    # by their middles: [400, 1000) lies in the window alone, [0, 100)
    # in the ingest, [200, 250) in the probe and [300, 320) in the publish
    # outside its children, both bench.publish to the harness
    assert [g[:2] for g in gaps["top"]] == [
        ["bench.window", "bench.window"], ["bench.ingest", "bench.ingest"],
        ["kpca.probe", "bench.publish"], ["kpca.publish", "bench.publish"]]
    assert [g[2] for g in gaps["top"]] == pytest.approx(
        [600e-9, 100e-9, 50e-9, 20e-9])
    assert gaps["idle_s_by_label"]["kpca.probe"] == pytest.approx(50e-9)
    maps = {"_batched_update": {"fusion.1": "kpca.rotate",
                                "while.2": "kpca.solve"}}
    st = tool.stage_ms(red, maps, steps=1)
    assert st["kpca.rotate"] == pytest.approx(100e-6)
    assert st["kpca.solve"] == pytest.approx(50e-6)
    assert st["unmatched"] == pytest.approx(80e-6)
    assert st["sum"] == pytest.approx(230e-6)


def test_clock_offsets_match_ring_and_trace():
    tool = _tool()
    recs = [_rec("kpca.query", 1.0, 2.0, query=1),
            _rec("kpca.query", 3.0, 4.0, query=2)]
    base = 500_000
    events = [(1_000_000 - base + 2000, 2_000_000 - base - 1000,
               "kpca.query", {"query": 1}),
              (3_000_000 - base - 4000, 4_000_000 - base,
               "kpca.query", {"query": 2})]
    got = tool.clock_offsets(recs, events, base)
    assert got["matched"] == 2
    assert got["start_us_max_abs"] == pytest.approx(4.0)
    assert got["end_us_max_abs"] == pytest.approx(1.0)


def test_tool_on_a_tiny_serve_cell():
    cell = "magic-8x2048.serve"
    res = _tool().analyse(cell, 20240607, 2.0, out=io.StringIO(),
                          config_overrides=tiny_config(cell),
                          traffic_overrides=TINY_TRAFFIC[cell],
                          require_tpu=False)
    assert res["correct"]
    assert res["clock"]["matched"] > 0
    assert res["clock"]["start_us_max_abs"] < 100.0
    c = res["counters"]
    assert c["traces_all_spans"] == c["host_traces"] > 0
    assert not any('span="none"' in k for k in c["delta"])
    # the publish's idle time now lies in its children, but for the few
    # microseconds between the harness's span and the program's
    labels = res["idle_gaps"]["idle_s_by_label"]
    kids = sum(labels.get(k, 0.0) for k in (
        "kpca.probe", "kpca.snapshot", "kpca.ref_spectrum"))
    assert labels.get("bench.publish", 0.0) < 0.05 * kids
    st = res["stages"]
    assert st["sum"] == pytest.approx(st["ingest_total"])
    assert st["kpca.solve"] > 0 and st["kpca.rotate"] > 0
    for name in ("query_host_ms", "query_trace_ms", "step_trace_ms",
                 "publish_host_ms"):
        assert res["metrics"][name] > 0
    assert res["metrics"]["query_trace_ms"] < res["metrics"]["query_host_ms"]
    assert res["end_to_end_traced"]["query_p95_ms"] > 0


def test_tool_without_a_tpu_gives_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run(
        [sys.executable, "bench/tools/program_trace.py", "--workload",
         "magic-8x2048.read", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 2
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr
