"""Exporters: Prometheus text exposition + JSONL event log + /metrics.

Two surfaces over one ``TelemetryHub``:

* ``to_prometheus(hub)`` renders the text exposition format
  (counters/gauges/histogram summaries); ``serve_metrics(hub, port)``
  serves it on ``GET /metrics`` from a daemon thread —
  ``serve.py --metrics-port P`` wires it up.
* ``write_jsonl(path, hub)`` dumps the events and program spans the
  hub's rings still hold plus one final ``scrape`` event;
  ``hub.open_jsonl(path)`` streams events live instead.  ``read_jsonl``
  / ``parse_prometheus`` close the round trip (and are what the exporter
  tests diff against).
"""
from __future__ import annotations

import json
import re
import threading


# ----------------------------------------------------------- Prometheus --
_LINE_RE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*(?:\{[^}]*\})?)\s+(\S+)$")


def _base_name(key: str) -> str:
    return key.partition("{")[0]


def _labeled(key: str, extra: dict) -> str:
    """Merge extra labels into an already-rendered key."""
    from repro.obs.hub import render_key

    base, _, rest = key.partition("{")
    labels = dict(extra)
    if rest:
        for part in rest.rstrip("}").split(","):
            k, _, v = part.partition("=")
            labels[k] = v.strip('"')
    return render_key(base, labels)


def to_prometheus(hub) -> str:
    """Text exposition: counters, gauges, and histograms as summaries
    (quantile-labelled series + _count/_sum, with the compile split as
    companion ``*_compiles`` / ``*_compile_ms`` series)."""
    lines: list[str] = []
    typed: set[str] = set()

    def header(key: str, kind: str):
        base = _base_name(key)
        if base not in typed:
            typed.add(base)
            lines.append(f"# TYPE {base} {kind}")

    with hub._lock:
        for key, c in sorted(hub._counters.items()):
            header(key, "counter")
            lines.append(f"{key} {c.value:g}")
        for key, g in sorted(hub._gauges.items()):
            header(key, "gauge")
            lines.append(f"{key} {g.value:g}")
        for key, h in sorted(hub._hists.items()):
            s = h.summary(key)
            header(key, "summary")
            for q, field in (("0.5", "p50"), ("0.9", "p90"),
                             ("0.99", "p99")):
                lines.append(f'{_labeled(key, {"quantile": q})} '
                             f'{s[f"{key}_{field}"]:g}')
            lines.append(f"{key}_count {len(h.ms):g}")
            lines.append(f"{key}_sum {sum(h.ms):g}")
            header(f"{key}_compiles", "counter")
            lines.append(f"{key}_compiles {len(h.compile_ms):g}")
            header(f"{key}_compile_ms", "counter")
            lines.append(f"{key}_compile_ms {sum(h.compile_ms):g}")
    return "\n".join(lines) + "\n"


def parse_prometheus(text: str) -> dict:
    """Inverse of ``to_prometheus`` for round-trip tests: rendered key →
    float value (comments/TYPE lines skipped)."""
    out: dict = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        mt = _LINE_RE.match(line)
        if mt:
            out[mt.group(1)] = float(mt.group(2))
    return out


# ----------------------------------------------------------------- JSONL --
def write_jsonl(path, hub) -> None:
    """Dump what the hub's rings hold — its events, then its program
    spans as ``{"event": "span", ...}`` — plus one final ``scrape`` event
    (the full registry, latency summaries included), one JSON object per
    line."""
    with open(path, "w") as f:
        for evt in list(hub.events):
            f.write(json.dumps(evt) + "\n")
        for rec in list(hub.spans):
            f.write(json.dumps({"event": "span", **rec}) + "\n")
        f.write(json.dumps({"event": "scrape", **hub.scrape()}) + "\n")


def read_jsonl(path) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# -------------------------------------------------------------- /metrics --
def serve_metrics(hub, port: int = 0):
    """Serve ``GET /metrics`` (Prometheus text) from a daemon thread.

    Returns the live ``HTTPServer`` — read the bound port from
    ``server.server_address[1]`` (pass ``port=0`` for an ephemeral one)
    and stop it with ``server.shutdown()``.
    """
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path.rstrip("/") not in ("", "/metrics", "/metric"):
                self.send_error(404)
                return
            body = hub.to_prometheus().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):                     # quiet scrapes
            pass

    server = ThreadingHTTPServer(("0.0.0.0", port), Handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    return server
