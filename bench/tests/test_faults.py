"""A whole run of each cell at a tiny size on the CPU, past the harness's
look for a chip, with the timed path broken underneath: the check must
come out false for every fault the cell can have, and true without one.

Faults: a step that returns its state unchanged; half of the batch left
out (half the tenants' points not folded, or half of a query's rows not
computed and the mean of the rest served in their place); an answer
altered where it is produced; a publish left out on every other step,
so that points wait for the next one.  The cells run on one chip, so there is no
exchange between chips to leave out.
"""
import io
import json

import numpy as np
import pytest

from conftest import TINY_TRAFFIC, tiny_config

import run as bench_run


def _run(cell, seed=20240607):
    out = io.StringIO()
    res = bench_run.run_cell(cell, seed, 2.0, False,
                             config_overrides=tiny_config(cell),
                             traffic_overrides=TINY_TRAFFIC[cell],
                             require_tpu=False, out=out)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == res
    return res


def _failed(res):
    return sorted(k for k, c in res["checks"].items()
                  if not c["value"] <= c["limit"])


def _step_unchanged(monkeypatch):
    from repro.core import engine

    monkeypatch.setattr(engine, "_batched_update",
                        lambda states, *a, **k: states)
    monkeypatch.setattr(engine, "_batched_downdate_masked",
                        lambda states, *a, **k: states)


def _half_tenants(monkeypatch):
    from repro.core import engine

    update = engine.StreamBatch.update

    def half(self, xs, active=None):
        act = np.arange(self.n_tenants) < self.n_tenants // 2
        return update(self, xs, active=act)

    monkeypatch.setattr(engine.StreamBatch, "update", half)


def _wrap_query(monkeypatch, change):
    from repro.core import serving

    query_batch = serving.query_batch

    def broken(*a, **k):
        return change(query_batch(*a, **k))

    monkeypatch.setattr(serving, "query_batch", broken)


def _half_rows(monkeypatch):
    def change(y):
        h = y.shape[1] // 2
        mean = y[:, :h].mean(axis=1, keepdims=True)
        return y.at[:, h:].set(np.broadcast_to(mean, y[:, h:].shape))

    _wrap_query(monkeypatch, change)


def _answer_altered(monkeypatch):
    _wrap_query(monkeypatch, lambda y: y.at[:, 0].set(y[:, 1]))


def _publish_skipped(monkeypatch):
    from repro.launch.serve import IngestServeLoop

    publish = IngestServeLoop.publish
    calls = [0]

    def every_other(self):
        calls[0] += 1
        return publish(self) if calls[0] % 2 else self.snaps

    monkeypatch.setattr(IngestServeLoop, "publish", every_other)


FAULTS = {"step_unchanged": _step_unchanged, "half_tenants": _half_tenants,
          "half_rows": _half_rows, "answer_altered": _answer_altered,
          "publish_skipped": _publish_skipped}
CELL_FAULTS = [
    ("magic-8x2048.serve", "step_unchanged"),
    ("magic-8x2048.serve", "half_tenants"),
    ("magic-8x2048.serve", "half_rows"),
    ("magic-8x2048.serve", "answer_altered"),
    ("magic-8x2048.serve", "publish_skipped"),
    ("magic-w8192.ingest", "step_unchanged"),
    ("magic-w8192.ingest", "publish_skipped"),
    ("magic-8x2048.read", "half_rows"),
    ("magic-8x2048.read", "answer_altered"),
]


@pytest.mark.parametrize("cell", sorted(TINY_TRAFFIC))
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], _failed(res)
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "setup_s" in res["metrics"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell,fault", CELL_FAULTS)
def test_fault_is_caught(monkeypatch, cell, fault):
    FAULTS[fault](monkeypatch)
    res = _run(cell)
    assert not res["correct"]
    assert _failed(res), res["checks"]
