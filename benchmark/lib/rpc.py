"""RPC reads and Prometheus parsing (copies of `chip_smoke.py`'s)."""

from __future__ import annotations

import json
import re
import urllib.error
import urllib.request


def http_get(url: str, timeout: float = 20.0, attempts: int = 3) -> tuple[int, bytes]:
    """One GET. A read that times out is asked again: once in some fifty
    runs the node left a `/metrics` read unanswered for a minute while it
    synced (my chip run, PR 23), and the harness's own reads must not lose
    the run to that."""
    for attempt in range(attempts):
        try:
            with urllib.request.urlopen(url, timeout=timeout) as resp:
                return resp.status, resp.read()
        except urllib.error.HTTPError as e:  # /health answers 503 with a body
            return e.code, e.read()
        except TimeoutError:
            if attempt == attempts - 1:
                raise
    raise AssertionError("unreachable")


def call(port: int, route: str) -> dict:
    """GET a JSON-RPC route by URI; returns its `result` or raises."""
    _, body = http_get(f"http://127.0.0.1:{port}/{route}")
    doc = json.loads(body)
    if "error" in doc:
        raise RuntimeError(f"{route}: {doc['error']}")
    return doc["result"]


_SERIES = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {name: [(labels, value), ...]}."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SERIES.match(line)
        if not m:
            continue
        labels = dict(_LABEL.findall(m.group(2) or ""))
        out.setdefault(m.group(1), []).append((labels, float(m.group(3))))
    return out


def metric(metrics: dict, name: str, **labels) -> float:
    """Sum of the series of `name` whose labels include `labels`."""
    return sum(
        v
        for ls, v in metrics.get(name, [])
        if all(ls.get(k) == str(want) for k, want in labels.items())
    )


def pull_metrics(port: int) -> dict:
    _, body = http_get(f"http://127.0.0.1:{port}/metrics")
    return parse_metrics(body.decode())


def rise(start: dict, end: dict, name: str, **labels) -> float:
    """How far a counter rose between two pulls."""
    return metric(end, name, **labels) - metric(start, name, **labels)
