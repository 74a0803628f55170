"""Test environment: force JAX onto a virtual 8-device CPU mesh so multi-chip
sharding paths compile and run without TPU hardware.

This file is also the tier-1 wiring for tmlint (tendermint_tpu/analysis/):
the three original collection lints are thin shims over the engine's rules
(M001 metric catalog, M002 span catalog, M003 kernel marks), the FULL rule
set gates collection on the package + tools/, and the runtime lock-rank
sanitizer (utils/lockrank.py) is enabled for the whole run — any rank
inversion or lock-order cycle a test provokes fails that test with the
acquisition-stack report.
"""

import os

# Must be set before jax initializes a backend.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Lock-rank sanitizer on for the whole suite (before any tendermint_tpu
# import constructs a lock). TENDERMINT_TPU_LOCKRANK=0 opts out locally.
os.environ.setdefault("TENDERMINT_TPU_LOCKRANK", "1")

import pathlib  # noqa: E402

import pytest  # noqa: E402

_REPO = pathlib.Path(__file__).resolve().parents[1]


def lint_kernel_marks(items) -> list[str]:
    """Marker lint shim: every `kernel`-marked test must ALSO be `slow`
    (tier-1 `-m 'not slow'` overrides pytest.ini's `-m 'not kernel'`;
    see the ROADMAP tier-1 note). Logic lives in tmlint rule M003."""
    from tendermint_tpu.analysis.rules_catalog import kernel_mark_offenders

    return kernel_mark_offenders(items)


def lint_metric_catalog(roots=None) -> list[str]:
    """Catalog lint shim (tmlint M001): every `tendermint_*` metric
    literal in the package (and tools/) must be registered by
    `telemetry/metrics.py`. Returns `path:name` offenders."""
    from tendermint_tpu.analysis.rules_catalog import metric_offenders

    return metric_offenders(roots)


def lint_span_catalog(roots=None) -> list[str]:
    """Span-name lint shim (tmlint M002): every literal passed to
    `TRACER.span("…")` / `TRACER.add("…", …)` must be in
    `telemetry/metrics.py`'s SPAN_CATALOG. Returns `path:name`
    offenders."""
    from tendermint_tpu.analysis.rules_catalog import span_offenders

    return span_offenders(roots)


def run_tmlint_gate() -> str | None:
    """Full tmlint pass over the package + tools with the repo baseline;
    returns the rendered report when it fails, None when clean. Gates
    tier-1 collection so concurrency/wire/purity invariants cannot
    regress silently (<2 s on the whole tree)."""
    from tendermint_tpu.analysis import engine

    report = engine.lint_paths(
        [_REPO / "tendermint_tpu", _REPO / "tools"],
        baseline_path=_REPO / "tools" / "tmlint_baseline.json",
        root=_REPO,
    )
    if report.ok:
        return None
    return engine.render_report(report)


def pytest_collection_modifyitems(config, items):
    bad = lint_kernel_marks(items)
    if bad:
        raise pytest.UsageError(
            "kernel-marked tests missing the slow mark (tier-1 `-m 'not "
            "slow'` would compile their XLA:CPU kernels): "
            + ", ".join(sorted(bad)[:10])
        )
    bad_metrics = lint_metric_catalog()
    if bad_metrics:
        raise pytest.UsageError(
            "tendermint_* metric names used in code but missing from "
            "telemetry/metrics.py's catalog: " + ", ".join(bad_metrics[:10])
        )
    bad_spans = lint_span_catalog()
    if bad_spans:
        raise pytest.UsageError(
            "span names recorded in code but missing from "
            "telemetry/metrics.py's SPAN_CATALOG: " + ", ".join(bad_spans[:10])
        )
    tmlint_failure = run_tmlint_gate()
    if tmlint_failure is not None:
        raise pytest.UsageError(
            "tmlint found repo-invariant violations (run `python -m "
            "tools.tmlint` locally; suppress false positives with a "
            "reasoned `# tmlint: disable=RULE -- why`):\n" + tmlint_failure
        )


@pytest.fixture(autouse=True)
def _lockrank_guard():
    """Turn lock-rank violations into failures of the test that
    provoked them, carrying both threads' acquisition stacks. Violations
    recorded by background threads between tests surface on the next
    test — still loud, occasionally mis-attributed by one test."""
    yield
    from tendermint_tpu.utils import lockrank

    violations = lockrank.drain()
    if violations:
        pytest.fail(
            "lock-rank sanitizer recorded violation(s) during this test "
            "(utils/lockrank.py):\n" + lockrank_render(violations),
            pytrace=False,
        )


def lockrank_render(violations) -> str:
    from tendermint_tpu.utils import lockrank

    return "\n".join(lockrank.render_violation(v) for v in violations)
