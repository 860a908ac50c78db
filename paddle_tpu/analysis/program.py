"""Orchestrators: one call per artifact kind, one report out.

``analyze_traced`` is the shared backend of
``Executor.analyze_program()`` and ``TracedFunction.analyze_program()``
— it takes an already-traced ``ClosedJaxpr`` (tracing is the caller's
job: ``jax.make_jaxpr`` over the cached pure function + avals, no XLA
compile) and runs the static audits.  ``analyze_runtime`` inspects the
live process (timeline events, executable caches) after some steps ran.
``lint_summary`` is the compact dict of a run's diagnostics; no program
reads it today (tests/test_analysis.py holds its shape).
"""
from __future__ import annotations

from collections import Counter

from .diagnostics import DiagnosticReport, get_log
from .dtype_audit import audit_jaxpr
from .host_sync import audit_host_sync
from .recompile import (audit_eager_cache, audit_executor_cache,
                        audit_trace_cache, audit_weak_types)

__all__ = ["analyze_traced", "analyze_runtime", "lint_summary"]


def analyze_traced(closed_jaxpr, label="", *, amp="auto",
                   executor_cache=None, trace_cache=None, emit=True,
                   mesh_plan=None, named_params=None):
    """Static audits over one traced program: weak types (TPU201),
    dtype/amp (TPU4xx), plus cache-churn audits when the owning cache
    is provided, plus sharding audits (TPU501/502) when the executor
    compiled under a mesh plan (``named_params`` is its
    ``[(name, shape, nbytes)]`` parameter inventory).  ``emit=True``
    records every finding to the process diagnostic log and the
    observability timeline."""
    report = DiagnosticReport(label=label)
    report.extend(audit_weak_types(closed_jaxpr, site=label))
    report.extend(audit_jaxpr(closed_jaxpr, amp=amp, site=label))
    if executor_cache is not None:
        report.extend(audit_executor_cache(executor_cache))
    if trace_cache is not None:
        report.extend(audit_trace_cache(trace_cache))
    if mesh_plan is not None and named_params:
        from .sharding_audit import audit_sharding
        report.extend(audit_sharding(mesh_plan, named_params,
                                     site=label))
    if emit:
        report.emit()
    return report


def analyze_runtime(events=None, budget=None, emit=True):
    """Audit the live process after steps ran: host-sync patterns over
    the obs timeline (TPU301/302) and churn in the executor + eager
    caches (TPU2xx)."""
    report = DiagnosticReport(label="runtime")
    report.extend(audit_host_sync(events, budget=budget))
    report.extend(audit_executor_cache())
    report.extend(audit_eager_cache())
    if emit:
        report.emit()
    return report


def lint_summary(events=None):
    """Compact lint state for artifacts: diagnostic counts by code
    (process log + a fresh non-emitting host-sync pass over ``events``)
    and per-kernel Pallas probe outcomes with the fallback reason."""
    counts = Counter(get_log().counts())
    if events is not None:
        for d in audit_host_sync(events):
            counts[d.code] += 1
    pallas = {}
    try:
        from ..ops.pallas_gate import probe_report
        for name, info in probe_report().items():
            # unprobed kernels are reported too — an all-fallback run
            # must be visible in the artifact, not an empty dict
            if not info.get("probed"):
                pallas[name] = {"probed": False}
                continue
            pallas[name] = {"probed": True, "ok": info["ok"]}
            if not info["ok"]:
                pallas[name]["error"] = (info.get("error") or "")[:200]
    except Exception:
        pass
    return {"counts": dict(counts), "pallas": pallas}
