"""paddle_tpu.obs — the unified observability layer (ISSUE 12).

One subsystem, three planes, one timeline:

- **Metrics** (:mod:`.metrics`): a process-global registry of named
  counters / gauges / log-bucketed histograms with frozen-tuple
  labels. Every legacy stats surface (``EngineLoad``,
  ``prefix_stats()``, ``spec_stats()``, ``overlap_stats()``, the
  ``health()`` envelopes, ``TrainTelemetry`` step times, the
  admission counters) is now a VIEW over this registry: old call
  signatures return their historical keys, the numbers live here.
  Built-in SLO histograms: ``serving_ttft_seconds``,
  ``serving_itl_seconds``, ``serving_queue_delay_seconds`` with
  p50/p95/p99 accessors (:func:`slo_summary`).
- **Traces** (:mod:`.trace`): Dapper-style per-request spans carried on
  ``GenRequest`` → cluster wire records → the disagg handoff payload
  header, collected in a bounded per-process ring, exported as Chrome
  trace-event JSON (Perfetto-loadable) and stitched across worker
  processes by trace_id.
- **Device/compile events** (:mod:`.compile`): the repository's one
  ``jax.monitoring`` listener turns each lowering and each backend
  compile into a finished span — ``to_static.lower`` /
  ``to_static.compile`` under the ``to_static.call`` that was open,
  ``xla.lower`` / ``xla.compile`` outside any — with the persistent
  cache's verdict (``cache``: hit / miss / off); dispatch→harvest spans
  from the serving engine's async copy ring; supervisor watchdog /
  rollback / chaos instants.

- **Reaction** (:mod:`.alerts`, :mod:`.regress` — ISSUE 15): the layer
  that converts the planes above into decisions. Declarative alert
  rules (thresholds, publisher-absence, multi-window SLO burn rates
  with per-(tenant, priority) error budgets) with a deterministic
  pending → firing → resolved lifecycle, surfaced through every
  ``health()`` envelope, the trace ring, and a JSONL journal; plus the
  schema'd bench ledger and its statistical perf-regression sentinel.

CLI: ``python -m paddle_tpu.obs dump|prom|trace|agg|alerts|top|regress``.
"""
from .alerts import (
    AbsenceRule,
    AlertManager,
    BurnRateRule,
    ThresholdRule,
    budget_remaining_frac,
    burn_rate,
    burn_rules_from_slo,
    default_manager,
    default_serving_rules,
    default_training_rules,
    set_default_manager,
)
from . import compile  # noqa: F401  (registers the jax.monitoring listener)
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricAttr,
    MetricsRegistry,
    labels_of,
    registry,
)
from .regress import (
    bench_record,
    detect_regressions,
    load_ledger,
    polarity_of,
)
from .trace import (
    Span,
    TraceRing,
    enabled,
    export_chrome_trace,
    finish_span,
    instant,
    new_trace_id,
    record_span,
    ring,
    set_enabled,
    set_process_label,
    span,
    start_span,
    stitch_traces,
    trace_ctx,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricAttr", "MetricsRegistry",
    "registry", "labels_of",
    "Span", "TraceRing", "new_trace_id", "span", "start_span",
    "finish_span", "record_span", "instant", "trace_ctx", "ring",
    "set_enabled",
    "enabled", "set_process_label", "export_chrome_trace",
    "stitch_traces",
    "slo_summary", "tenant_slo_table",
    "HEALTH_SCHEMA_VERSION", "health_envelope",
    "ThresholdRule", "AbsenceRule", "BurnRateRule", "AlertManager",
    "burn_rate", "budget_remaining_frac", "burn_rules_from_slo",
    "default_serving_rules", "default_training_rules",
    "default_manager", "set_default_manager",
    "bench_record", "load_ledger", "detect_regressions", "polarity_of",
]

# SLO histograms the serving engine feeds (seconds)
SLO_HISTOGRAMS = (
    "serving_ttft_seconds",
    "serving_itl_seconds",
    "serving_queue_delay_seconds",
)


def slo_summary(*, by_tenant: bool = False) -> dict:
    """p50/p95/p99 + count for the built-in TTFT / inter-token-latency
    / queue-delay histograms, aggregated over every label set. The SLO
    series carry a ``tenant`` label (default tenant ``"default"``), so
    the label sets PARTITION the observations and the merged totals
    stay exact. ``by_tenant=True`` adds a ``"tenants"`` key: per-tenant
    sub-summaries (same shape per metric), with every past-the-cap
    overflow handle folded into one ``"(overflow)"`` tenant."""
    out = {}
    tenants: dict = {}
    reg = registry()
    for name in SLO_HISTOGRAMS:
        agg = Histogram()
        m = reg._metrics.get(name)
        if m is not None:
            for labels, h in list(m.series.items()):
                agg.merge(h)
                if by_tenant:
                    t = dict(labels).get("tenant", "default")
                    bucket = tenants.setdefault(t, {}).setdefault(
                        name, Histogram())
                    bucket.merge(h)
            for h in list(m.overflow):
                agg.merge(h)
                if by_tenant:
                    bucket = tenants.setdefault("(overflow)", {}).setdefault(
                        name, Histogram())
                    bucket.merge(h)
        out[name] = agg.to_dict()
    if by_tenant:
        out["tenants"] = {
            t: {name: h.to_dict() for name, h in sorted(per.items())}
            for t, per in sorted(tenants.items())
        }
    return out


def tenant_slo_table() -> dict:
    """Compact per-tenant SLO view for the health() surfaces: requests
    submitted (``serving_tenant_requests_total``) plus TTFT/ITL p50 and
    p99 per tenant. Tenants past the registry cardinality cap fold into
    ``"(overflow)"`` — visible, counted, never unbounded."""
    full = slo_summary(by_tenant=True)
    reg = registry()
    req_by_tenant: dict = {}
    m = reg._metrics.get("serving_tenant_requests_total")
    if m is not None:
        for labels, h in list(m.series.items()):
            t = dict(labels).get("tenant", "default")
            req_by_tenant[t] = req_by_tenant.get(t, 0) + int(h.value)
        if m.overflow:
            req_by_tenant["(overflow)"] = req_by_tenant.get(
                "(overflow)", 0) + int(sum(h.value for h in m.overflow))
    out = {}
    for t in sorted(set(full.get("tenants", {})) | set(req_by_tenant)):
        per = full.get("tenants", {}).get(t, {})
        ttft = per.get("serving_ttft_seconds", {})
        itl = per.get("serving_itl_seconds", {})
        out[t] = {
            "requests": req_by_tenant.get(t, 0),
            "ttft_p50": ttft.get("p50"), "ttft_p99": ttft.get("p99"),
            "itl_p50": itl.get("p50"), "itl_p99": itl.get("p99"),
        }
    return out


# ---------------------------------------------------------------------------
# The shared health() envelope (ISSUE 12 satellite: the two-shapes
# drift fix). Every health() surface wraps its legacy payload with the
# same versioned top-level keys, each sourced from the registry.

HEALTH_SCHEMA_VERSION = 1

# the common top-level keys every health() shape now carries, beyond
# its legacy payload; the schema regression test pins this exact set
HEALTH_COMMON_KEYS = ("schema_version", "kind", "shed_total",
                      "expired_total", "requests_total", "alerts")


def health_envelope(kind: str, payload: dict) -> dict:
    """Wrap one surface's legacy health payload with the shared,
    registry-sourced envelope keys — including the process-default
    alert manager's compact summary (ISSUE 15), so an SLO burn or a
    silenced replica is visible from EVERY health() surface. Legacy
    keys stay at the top level (old readers keep indexing them); the
    envelope keys win on collision only for
    ``schema_version``/``kind``."""
    from . import alerts as _alerts  # lazy: alerts imports .slo

    reg = registry()
    out = dict(payload)
    out["schema_version"] = HEALTH_SCHEMA_VERSION
    out["kind"] = str(kind)
    out["shed_total"] = int(reg.total("serving_shed_total"))
    out["expired_total"] = int(reg.total("serving_expired_total"))
    out["requests_total"] = int(reg.total("serving_requests_total"))
    out["alerts"] = _alerts.health_summary()
    return out
