"""Reduction from a profiler trace to the numbers the per-layer metrics
read: device busy time as the union of the intervals in which an
operation ran, the idle share, device time by operation and by program
(module), and the benchmark's own host spans on the same clock.

The harness wraps its calls into each layer in
`jax.profiler.TraceAnnotation("bench.<layer>")`; the whole measured
stretch is `bench.window`. Device planes are `/device:<chip>:<n>`;
their "XLA Ops" line holds one event per operation run and their
"XLA Modules" line one per program run.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
from collections import defaultdict

SPAN_PREFIX = "bench."


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                      # mean over the chips used
    ops_s: dict                        # op name -> device seconds
    modules_s: dict                    # program name -> device seconds
    module_runs: dict                  # program name -> number of runs
    spans: dict                        # bench span -> [(start_ns, end_ns)]
    idle_by_host: dict                 # host activity -> idle seconds
    n_devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def span_s(self, name: str) -> float:
        return sum(e - s for s, e in self.spans.get(name, ())) / 1e9

    def module_s(self, pattern: str) -> float:
        """Device seconds of every program whose name contains
        `pattern`."""
        return sum(v for k, v in self.modules_s.items() if pattern in k)


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the enclosed block into `log_dir`: device activity and
    host annotations, without the Python function tracer (which records
    every Python call and would slow the host path being measured)."""
    import jax
    os.makedirs(log_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """A host span on the profiler's clock (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def latest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no profile under {log_dir}")
    return paths[-1]


def op_name(hlo: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    """Merge [(start, end)] into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def reduce(path: str) -> TraceSummary:
    """Reduce one `.xplane.pb` file (see `reduce_data`)."""
    from jax.profiler import ProfileData
    return reduce_data(ProfileData.from_file(path))


def reduce_data(data) -> TraceSummary:
    """Reduce a `jax.profiler.ProfileData` to a `TraceSummary` over the
    window that the `bench.window` span marks (the whole trace when it
    is absent)."""
    spans = defaultdict(list)
    host_events = []                   # (start, end, name): python thread
    devices = []                       # per device: (ops, modules)
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name.startswith(SPAN_PREFIX):
                        spans[ev.name[len(SPAN_PREFIX):]].append((s, e))
                    elif line.name == "python":
                        host_events.append((s, e, ev.name))
        elif _is_device_plane(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                op_name(ev.name)) for ev in line.events)
                elif line.name == "XLA Modules":
                    modules.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                    ev.name) for ev in line.events)
            if ops or modules:
                devices.append((ops, modules))
    window = spans.pop("window", None)
    if window:
        lo, hi = window[0][0], window[-1][1]
    else:
        every = [(s, e) for ops, mods in devices for s, e, _ in ops + mods]
        every += [(s, e) for s, e, _ in host_events]
        lo = min(s for s, _ in every)
        hi = max(e for _, e in every)
    ops_s, modules_s, module_runs = defaultdict(float), defaultdict(float), \
        defaultdict(int)
    busy_total = 0.0
    gaps = []
    for ops, modules in devices:
        base = ops if ops else modules
        busy = _union(_clip([(s, e) for s, e, _ in base], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        prev = lo
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if hi > prev:
            gaps.append((prev, hi))
        for s, e, name in ops:
            if e > lo and s < hi:
                ops_s[name] += (min(e, hi) - max(s, lo)) / 1e9
        for s, e, name in modules:
            if e > lo and s < hi:
                modules_s[name] += (min(e, hi) - max(s, lo)) / 1e9
                module_runs[name] += 1
    n_dev = max(len(devices), 1)
    return TraceSummary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_total / n_dev / 1e9,
        ops_s=dict(ops_s), modules_s=dict(modules_s),
        module_runs=dict(module_runs),
        spans={k: sorted(v) for k, v in spans.items()},
        idle_by_host=_attribute_gaps(gaps, spans, host_events, n_dev),
        n_devices=len(devices))


def _attribute_gaps(gaps, spans, host_events, n_dev) -> dict:
    """Idle device seconds by what the host was doing: the innermost
    bench span around the gap's midpoint, and the host call on the
    python thread that covers most of the gap."""
    flat = [(s, e, name) for name, iv in spans.items() for s, e in iv]
    flat.sort()
    host_events.sort()
    starts = [s for s, _, _ in host_events]
    import bisect
    out = defaultdict(float)
    for g0, g1 in gaps:
        mid = (g0 + g1) / 2
        inner = [(e - s, n) for s, e, n in flat if s <= mid <= e]
        label = min(inner)[1] if inner else "outside spans"
        best, best_cover = None, 0
        i = bisect.bisect_right(starts, g1)
        for s, e, name in host_events[max(0, i - 200):i]:
            cover = min(e, g1) - max(s, g0)
            if cover > best_cover:
                best, best_cover = name, cover
        if best is not None and best_cover > 0.5 * (g1 - g0):
            label = f"{label} / {best}"
        out[label] += (g1 - g0) / 1e9 / n_dev
    return dict(out)


def top(seconds_by_name: dict, n: int = 8) -> list:
    return [[k, round(v, 6)] for k, v in
            sorted(seconds_by_name.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    """The device operations that took most time and the idle time by
    host activity, each list at most `top` long."""
    ops = sorted(summary.ops_s.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(summary.idle_by_host.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
