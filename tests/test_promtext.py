"""Prometheus text-exposition validation (ISSUE-6 satellite).

A minimal parser for the Prometheus text format, run against the FULL
``/metrics`` output of the frontend (ServiceMetrics + phase histograms +
process identity), the worker-metrics aggregator (components/metrics.py),
and the cluster telemetry aggregator — so a future metric addition that
ships malformed exposition (bad name, missing HELP/TYPE, broken label
escaping, duplicate family) fails tier-1 instead of a production scrape.

The dynlint ``metric-name-valid`` rule checks *registration sites*
statically; this checks what actually renders, catching hand-built
exposition lines (f-string renderers) the AST rule can't see.
"""

from __future__ import annotations

import math

import pytest

from dynamo_tpu.components.metrics import MetricsAggregator
from dynamo_tpu.components.telemetry_aggregator import ClusterTelemetry
from dynamo_tpu.components.mock_worker import MockWorkerStats
from dynamo_tpu.kv_router.protocols import ForwardPassMetrics
from dynamo_tpu.llm.http.metrics import ServiceMetrics
from dynamo_tpu.runtime import telemetry, tracing

from .promtext import PromParseError, parse_prometheus_text

class TestParserRejectsMalformed:
    @pytest.mark.parametrize("bad", [
        "# HELP ok help\n# TYPE ok gauge\nok{unclosed 1",
        "# HELP ok help\n# TYPE ok gauge\nok{a=unquoted} 1",
        "# HELP ok help\n# TYPE ok gauge\nok notanumber",
        "# HELP ok help\n# TYPE ok gauge\nok 1\n# HELP ok again\nok 2",
        "# HELP 0bad help\n# TYPE 0bad gauge\n",
        "# HELP ok  \n# TYPE ok gauge\nok 1",       # empty HELP
        "# HELP ok h\n# TYPE ok wat\nok 1",          # unknown TYPE
        "ok 1",                                       # no metadata at all
        '# HELP ok h\n# TYPE ok gauge\nok{a="1",a="2"} 1',  # dup label
    ])
    def test_rejects(self, bad):
        with pytest.raises(PromParseError):
            parse_prometheus_text(bad)

    def test_accepts_escapes_and_inf(self):
        text = (
            "# HELP h histogram\n# TYPE h histogram\n"
            'h_bucket{le="+Inf",m="a\\"b\\\\c\\nd"} 3\n'
            "h_sum 1.5\nh_count 3\n"
        )
        fams = parse_prometheus_text(text)
        (name, labels, value) = fams["h"]["samples"][0]
        assert labels["m"] == 'a\\"b\\\\c\\nd'
        assert value == math.inf or value == 3  # bucket count value


# -- full expositions --------------------------------------------------------


@pytest.fixture(autouse=True)
def _fresh_planes():
    tracing.configure()
    telemetry.configure()
    yield
    tracing.configure()
    telemetry.configure()


def _exercised_frontend() -> ServiceMetrics:
    m = ServiceMetrics()
    # nasty label values: quotes, backslashes, newlines must all escape
    for model in ("llama-8b", 'we"ird\\mo\ndel'):
        with m.inflight_guard(model, "chat/completions", "stream") as g:
            g.mark_chunk()
            g.mark_chunk()
            g.count_tokens(5)
            g.mark_ok()
        with m.inflight_guard(model, "completions", "unary") as g:
            g.mark_shed()
    tracing.observe_phase("ttft", 0.2)
    tracing.observe_phase("decode", 1.2)
    return m

def test_frontend_metrics_exposition_valid():
    fams = parse_prometheus_text(_exercised_frontend().render())
    for family in (
        "dynamo_frontend_requests_total",
        "dynamo_frontend_inflight_requests",
        "dynamo_frontend_request_duration_seconds",
        "dynamo_frontend_time_to_first_token_seconds",
        "dynamo_frontend_inter_token_latency_seconds",
        "dynamo_frontend_overloaded_total",
        "dynamo_phase_latency_seconds",
        "dynamo_uptime_seconds",
        "dynamo_build_info",
    ):
        assert family in fams, f"missing family {family}"
        assert fams[family]["samples"], f"no samples for {family}"
    # histograms carry the full bucket/sum/count triplet
    names = {n for (n, _, _) in fams["dynamo_phase_latency_seconds"]["samples"]}
    assert names == {
        "dynamo_phase_latency_seconds_bucket",
        "dynamo_phase_latency_seconds_sum",
        "dynamo_phase_latency_seconds_count",
    }


def test_worker_aggregator_exposition_valid():
    agg = MetricsAggregator("name\\sp\"ace")
    stats = MockWorkerStats(seed=1)
    stats.tick(requests=12)
    agg.update("w-1", ForwardPassMetrics.from_dict(stats.metrics("m1").to_dict()))
    agg.update('w"2', ForwardPassMetrics(uptime_s=3.0))
    agg.record_hit_rate("w-1", isl_blocks=8, overlap_blocks=4)
    fams = parse_prometheus_text(agg.render())
    for family in (
        "dynamo_worker_request_active_slots",
        "dynamo_worker_kv_total_blocks",
        "dynamo_worker_health_state",
        "dynamo_worker_decode_tokens_per_s",
        "dynamo_worker_step_time_ms",
        "dynamo_worker_batch_slot_util",
        "dynamo_worker_jit_recompiles",
        "dynamo_worker_kv_peak_occupancy_perc",
        "dynamo_worker_requests_total",
        "dynamo_worker_requests_errored",
        "dynamo_worker_kv_integrity_failures_total",
        "dynamo_worker_watchdog_trips_total",
        "dynamo_worker_phase_latency_ms",
        "dynamo_worker_uptime_seconds",
        "dynamo_worker_up",
        "dynamo_uptime_seconds",
        "dynamo_build_info",
    ):
        assert family in fams, f"missing family {family}"


def test_cluster_telemetry_exposition_valid():
    ct = ClusterTelemetry(
        "ns", policy=telemetry.TelemetryPolicy(
            fast_window=10, mid_window=20, slow_window=40,
        ),
    )
    stats = MockWorkerStats(seed=2)
    stats.tick(requests=12)
    ct.ingest("w1", ForwardPassMetrics.from_dict(stats.metrics("m1").to_dict()))
    fams = parse_prometheus_text(ct.render_prometheus())
    for family in (
        "dynamo_cluster_workers",
        "dynamo_cluster_headroom_frac",
        "dynamo_cluster_slo_compliance",
        "dynamo_cluster_slo_burn_rate",
        "dynamo_cluster_slo_alert",
        "dynamo_cluster_kv_integrity_failures_total",
        "dynamo_cluster_watchdog_trips_total",
        "dynamo_cluster_workers_quarantined",
        "dynamo_cluster_workers_suspect",
    ):
        assert family in fams, f"missing family {family}"


def test_frontend_with_cluster_section_still_valid():
    """A co-hosted aggregator's cluster section rides the frontend
    exposition without breaking it (or duplicating families)."""
    ct = ClusterTelemetry(
        "ns", policy=telemetry.TelemetryPolicy(
            fast_window=10, mid_window=20, slow_window=40,
        ),
    )
    stats = MockWorkerStats(seed=3)
    stats.tick()
    ct.ingest("w1", ForwardPassMetrics.from_dict(stats.metrics("m1").to_dict()))
    telemetry.set_cluster(ct)
    try:
        fams = parse_prometheus_text(_exercised_frontend().render())
    finally:
        telemetry.set_cluster(None)
    assert "dynamo_cluster_workers" in fams
    assert "dynamo_frontend_requests_total" in fams


def test_quarantined_worker_exposition_valid():
    """A quarantined mock worker (the TPU-less drill: --health-state
    quarantined --integrity-failures N) renders grammar-valid worker AND
    cluster expositions with the integrity families populated."""
    agg = MetricsAggregator("ns")
    stats = MockWorkerStats(
        seed=4, integrity_failures=7, watchdog_trips=2,
        health_state="quarantined",
    )
    stats.tick(requests=3)
    m = ForwardPassMetrics.from_dict(stats.metrics("m1").to_dict())
    agg.update("w-bad", m)
    text = agg.render()
    fams = parse_prometheus_text(text)
    assert fams["dynamo_worker_kv_integrity_failures_total"]["samples"]
    # quarantined maps to health_state 3 (graver than unhealthy=2)
    assert 'dynamo_worker_health_state{namespace="ns",worker="w-bad"} 3' \
        in text

    ct = ClusterTelemetry(
        "ns", policy=telemetry.TelemetryPolicy(
            fast_window=10, mid_window=20, slow_window=40,
        ),
    )
    ct.ingest("w-bad", m)
    cfams = parse_prometheus_text(ct.render_prometheus())
    assert cfams["dynamo_cluster_workers_quarantined"]["samples"]
    assert cfams["dynamo_cluster_kv_integrity_failures_total"]["samples"]


def test_suspect_worker_exposition_valid():
    """A fail-slow-suspect mock worker (the TPU-less drill:
    --straggler-state suspect --dispatch-us-per-token N --health-state
    suspect) renders grammar-valid worker AND cluster expositions with
    the straggler families populated and the exact state values the
    runbook greps for."""
    agg = MetricsAggregator("ns")
    stats = MockWorkerStats(
        seed=5, dispatch_us_per_token=900.0, straggler_state="suspect",
        health_state="suspect",
    )
    stats.tick(requests=3)
    m = ForwardPassMetrics.from_dict(stats.metrics("m1").to_dict())
    agg.update("w-slow", m)
    text = agg.render()
    fams = parse_prometheus_text(text)
    for family in (
        "dynamo_worker_dispatch_us_per_token_ewma",
        "dynamo_worker_straggler_samples_total",
        "dynamo_worker_straggler_state",
    ):
        assert family in fams, f"missing family {family}"
        assert fams[family]["samples"], f"no samples for {family}"
    # suspect maps to its own health value (4) — the soft state must
    # never fall through the unknown-state default to unhealthy=2
    assert 'dynamo_worker_health_state{namespace="ns",worker="w-slow"} 4' \
        in text
    assert 'dynamo_worker_straggler_state{namespace="ns",worker="w-slow"} 1' \
        in text

    ct = ClusterTelemetry(
        "ns", policy=telemetry.TelemetryPolicy(
            fast_window=10, mid_window=20, slow_window=40,
        ),
    )
    ct.ingest("w-slow", m)
    cfams = parse_prometheus_text(ct.render_prometheus())
    assert cfams["dynamo_cluster_workers_suspect"]["samples"]
    roll = ct.rollup()
    entry = roll["models"]["m1"]
    assert entry["workers_suspect"] == 1
    assert entry["straggler_worker_ids"] == ["w-slow"]
