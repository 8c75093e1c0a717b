"""repro.telemetry — device-resident metrics, span tracing, exporters.

``Telemetry`` is the one object the serving stack threads around: a
metric ``Instruments`` surface over the declare-once ``REGISTRY``, a
``SpanTracer`` for the per-request timeline, and the per-event sample
series the Chrome-trace counter tracks are built from. The scheduler
calls ``event()`` exactly once per scheduler event — that initiates the
ONE (non-blocking) device drain telemetry costs per event, audited by
the drain counter — and ``finalize()`` once at end of run to land the
queued drains and resolve lazy span attribution, off the serving path.

Telemetry is strictly additive: with ``telemetry=None`` (the default
everywhere) no instrument, span or drain exists and every run is
bit-identical to the pre-telemetry code path; with it on, the compiled
computations and the RNG key schedule are untouched, so tokens and
WriteStats stay bit-identical too (asserted in tests).

``scopes`` declares the named device scopes and host phases that put the
serving stack's layers on a ``jax.profiler`` trace's clock.
"""
from __future__ import annotations

from typing import Any, Dict

from repro.telemetry.export import (chrome_trace, metrics_json,
                                    prometheus_text, validate_json,
                                    validate_timeline, write_metrics,
                                    write_timeline)
from repro.telemetry.registry import (COUNTER, GAUGE, HISTOGRAM,
                                      Instruments, MetricRegistry,
                                      MetricSpec, REGISTRY)
from repro.telemetry.report import render_report
from repro.telemetry.spans import (LANE_BACKGROUND, LANE_SERVE, Lazy,
                                   Span, SpanTracer)


class Telemetry:
    """The per-run telemetry context (instruments + tracer + series)."""

    def __init__(self, registry: MetricRegistry = None):
        self.instruments = Instruments(registry)
        self.tracer = SpanTracer()
        self.series = []  # one drained sample row per scheduler event
        self.events = 0

    def event(self, clock: float, **gauges: float) -> Dict[str, float]:
        """One scheduler event: set the sampled gauges, initiate the
        event's non-blocking instrument drain, append the sample row to
        the series (device columns land in place at ``finalize``). The
        scheduler calls this exactly once per loop event — the
        telemetry sync budget."""
        self.instruments.set("serve_clock_steps", clock)
        for name, v in gauges.items():
            self.instruments.set(name, v)
        self.instruments.inc("serve_events_total")
        row = self.instruments.drain()
        self.series.append(row)
        self.events += 1
        return row

    def finalize(self) -> None:
        """Land the queued instrument drains and resolve lazy device
        span args — one landing pass each, strictly after the run."""
        self.instruments.resolve()
        self.tracer.finalize()

    def snapshot(self) -> Dict[str, Any]:
        """The serve-report section: everything an exporter needs."""
        self.finalize()
        drains = self.instruments.drains
        return {
            "events": self.events,
            "spans": len(self.tracer.spans),
            "drains_per_event": drains / max(self.events, 1),
            "metrics": self.instruments.snapshot(),
            "series": self.series,
            "spans_detail": self.tracer.snapshot(),
        }


__all__ = [
    "Telemetry", "Instruments", "MetricRegistry", "MetricSpec",
    "REGISTRY", "COUNTER", "GAUGE", "HISTOGRAM",
    "SpanTracer", "Span", "Lazy", "LANE_SERVE", "LANE_BACKGROUND",
    "chrome_trace", "prometheus_text", "metrics_json",
    "write_timeline", "write_metrics", "validate_json",
    "validate_timeline", "render_report",
]
