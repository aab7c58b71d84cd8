"""The trace reduction, on synthetic event lists and on a tiny trace
profiled on the CPU inside the test."""
import pytest

from harness import layers, trace as ht
from harness.trace import Op, Span, Trace


def _trace():
    dev = "/device:TPU:0"
    tr = Trace()
    # Two program runs: an ingest step [100, 400) and a query [600, 700).
    tr.modules = [Op(100, 400, "jit__batched_update(1)", "jit__batched_update(1)", "", dev),
                  Op(600, 700, "jit_dot_general(2)", "jit_dot_general(2)", "", dev)]
    tr.ops = [Op(100, 250, "fusion.1", "", "convolution fusion", dev),
              Op(200, 300, "fusion.2", "", "loop fusion", dev),   # overlaps
              Op(350, 400, "while.3", "", "control flow", dev),
              Op(600, 700, "dot.4", "", "convolution", dev)]
    tr.spans = [Span(0, 1000, "bench.window"),
                Span(50, 450, "bench.ingest"),
                Span(300, 450, "bench.publish"),
                Span(450, 590, "bench.wait"),
                Span(590, 720, "bench.query")]
    ht.attribute(tr)
    return tr


def test_union_and_gaps():
    assert ht.union_ns([(0, 10), (5, 20), (30, 40)], 0, 100) == 30
    assert ht.union_ns([(0, 10), (5, 20)], 8, 12) == 4
    assert ht.gaps([(10, 20), (15, 30), (50, 60)], 0, 100) == [
        (0, 10), (30, 50), (60, 100)]


def test_ops_get_their_module_and_span():
    tr = _trace()
    mods = [o.module for o in tr.ops]
    assert mods[:3] == ["jit__batched_update(1)"] * 3
    assert mods[3] == "jit_dot_general(2)"
    assert [o.span for o in tr.ops] == ["bench.ingest", "bench.ingest",
                                        "bench.publish", "bench.query"]


def test_reduced_busy_idle_and_breakdown():
    red = ht.Reduced(_trace(), 0, 1000)
    # busy: [100, 300) + [350, 400) + [600, 700) = 350 of 1000
    assert red.busy_ns == 350
    assert red.idle_pct() == pytest.approx(65.0)
    top = red.top_ops(2)
    assert top[0] == ["jit__batched_update(1)/fusion.1", 150e-9]
    gaps = red.idle_gaps(4)
    # [700, 1000), [400, 600), [0, 100), [300, 350), by the span at the middle
    assert gaps[0] == ["bench.window", 300e-9]
    assert [g[0] for g in gaps[1:]] == ["bench.wait", "bench.ingest",
                                        "bench.publish"]


def test_layer_helpers():
    tr = _trace()
    ingest = [o for o in tr.ops if layers.is_ingest(o)]
    assert len(ingest) == 3
    assert [layers.is_dot(o) for o in ingest] == [True, False, False]

    class R:
        trace = ht.Reduced(tr, 0, 1000)
        steps = 1

    assert layers.per_step_ms(R, layers.is_ingest) == pytest.approx(300e-6)


def test_no_ops_no_idle_share():
    assert ht.Reduced(Trace(), 0, 10).idle_pct() is None


def test_cpu_trace_is_read(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    cap = {}
    with ht.capture(cap):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.query"):
                    f(x).block_until_ready()
    tr = ht.extract(cap["path"])
    ht.cleanup(cap)
    win = [s for s in tr.spans if s.name == "bench.window"]
    assert len(win) == 1
    assert len([s for s in tr.spans if s.name == "bench.query"]) == 3
    red = ht.Reduced(tr, win[0].start, win[0].end)
    assert red.ops and all(o.module for o in red.ops)
    in_query = [o for o in red.ops if o.span == "bench.query"]
    assert in_query and all("jit_" in o.module for o in in_query)
    assert 0.0 < red.idle_pct() < 100.0
    assert 0.0 < red.busy_s < red.window_s


@pytest.mark.parametrize("name,dot", [
    ("%fusion.184 = f32[8,2048,2048]{1,2,0:T(8,128)} fusion(f32[8,2048,2048]"
     "{2,1,0:T(8,128)} %bitcast.33), kind=kOutput, calls=%fused_computation",
     True),
    ("%convolution.3 = f32[256,8]{1,0} convolution(f32[256,2048]{1,0} %a, "
     "f32[2048,8]{1,0} %b), dim_labels=bf_io->bf", True),
    ("%fusion.674 = f32[8,2048]{1,0:T(8,128)S(1)} fusion(f32[8,2048] %g), "
     "kind=kLoop, calls=%fused_computation.5", False),
    ("%copy.161 = f32[8,2048]{1,0} copy(f32[8,2048]{1,0} %g)", False),
    ("dot_general.1", True),
])
def test_dot_ops_by_their_tpu_and_cpu_names(name, dot):
    assert layers.is_dot(Op(0, 1, name, "m", "", "d")) is dot
