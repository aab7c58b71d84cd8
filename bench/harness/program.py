"""The program's own spans and stage names, for the readers and tools.

``repro.obs.span`` keeps a record of each closed program span on the
process hub's span ring (``TelemetryHub.spans``): its name, start and
end (``time.time_ns``), its parent, its ids (``step``: the ingest step
number of ``IngestServeLoop``, shared by ``kpca.ingest`` and its
children; ``query``: the query sequence number of ``kpca.query``) and
the JAX traces, trace seconds and compiles booked to it while it was the
innermost open span.  The readers run in the process that ran the
window, after it and before the check, so the window's requests are the
last ``run.steps`` step ids and the last ``run.queries`` query ids on
the ring.  A program that keeps no such ring gives None.

The device programs name their stages with ``jax.named_scope``
(``STAGES``); a TPU trace's op events carry only the HLO instruction, so
a stage is found from the ``op_name`` metadata of the compiled module's
HLO text, by instruction name (``hlo_stages``).
"""
from __future__ import annotations

import re

STAGES = ("kpca.krow", "kpca.solve", "kpca.rotate", "kpca.downdate")
UNSCOPED = "unscoped"


def ring():
    """The process hub's span records (None where the program has none)."""
    from repro import obs

    spans = getattr(obs.get_hub(), "spans", None)
    return None if spans is None else list(spans)


def window_records(run, key: str, records=None):
    """Records of the window's requests by id ``key`` ('step' or 'query'):
    the trailing records whose id is among the last ``run.steps`` /
    ``run.queries`` (ids grow with each request of one service loop)."""
    recs = ring() if records is None else records
    n = run.steps if key == "step" else run.queries
    keyed = [r for r in recs or () if key in r]
    if not keyed or not n:
        return None
    first = keyed[-1][key] - n
    out = []
    for r in reversed(keyed):
        if r[key] <= first:
            break
        out.append(r)
    return out[::-1]


def dur_ms(rec) -> float:
    return (rec["end_ns"] - rec["start_ns"]) / 1e6


def stage_of(op_name: str) -> str:
    """The innermost stage named in a scope path, else ``UNSCOPED``."""
    best, at = UNSCOPED, -1
    for s in STAGES:
        i = op_name.rfind(s)
        if i > at:
            best, at = s, i
    return best


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                    r'metadata=\{op_name="([^"]*)"')


def hlo_stages(hlo_text: str) -> dict:
    """Instruction name -> stage, from a compiled module's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = stage_of(m.group(2))
    return out


def instruction(op_name: str) -> str:
    """'fusion.12' from a TPU op's HLO text ('%fusion.12 = f32[...] ...')
    or a CPU op's name ('fusion.12')."""
    return op_name.split(" = ", 1)[0].strip().lstrip("%")
