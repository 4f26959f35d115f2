"""Streaming telemetry: typed probes, structured event tracing, and
exporters (JSONL / Chrome trace / CSV / npz / Prometheus text), plus
program spans on the profiler's clock.

See `repro.telemetry.hub` for the probe taxonomy,
`repro.telemetry.export` for the export surfaces and
`repro.telemetry.spans` for the spans.
"""
from repro.telemetry.hub import (
    NULL_HUB,
    Counter,
    Gauge,
    NullHub,
    TelemetryHub,
    Timeline,
    WindowedSeries,
    hist_bin_index,
    hist_bin_upper,
)
from repro.telemetry.export import (
    EVENT_SCHEMA_VERSION,
    chrome_trace,
    export_run,
    prometheus_text,
    read_jsonl,
    series_to_csv,
    series_to_npz,
    start_metrics_server,
    write_chrome_trace,
    write_jsonl,
)
from repro.telemetry.spans import Phase, span

__all__ = [
    "TelemetryHub", "NullHub", "NULL_HUB", "Counter", "Gauge",
    "WindowedSeries", "Timeline", "hist_bin_index", "hist_bin_upper",
    "EVENT_SCHEMA_VERSION", "write_jsonl", "read_jsonl", "chrome_trace",
    "write_chrome_trace", "series_to_csv", "series_to_npz",
    "prometheus_text", "export_run", "start_metrics_server",
    "Phase", "span",
]
