"""Program spans (`repro.telemetry.spans`): where they sit in a profile of
the fleet engine, the event runner and the serving engine, the counts
they carry, and that profiling changes no result.

Each test that profiles starts and stops its own trace on the host CPU
and reads it back with `jax.profiler.ProfileData`.
"""
from __future__ import annotations

import glob
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.sim import ExperimentConfig, run_experiment
from repro.telemetry import Phase, TelemetryHub, chrome_trace, span
from repro.workloads import get_scenario

#: the program's layers, the first dotted part of every span name
LAYERS = ("workloads", "sim", "fleetsim", "engine")
FLEET_SPANS = {"fleetsim.experiment", "fleetsim.build", "fleetsim.bin",
               "fleetsim.compile", "fleetsim.scan", "fleetsim.unpack",
               "fleetsim.collect"}


def _profile(log_dir, fn):
    """Run `fn()` under the profiler; return (its result, the program
    spans of the profile as [(name, start_ns, end_ns, {argument:
    value})] in start order)."""
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=options)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.partition(".")[0] in LAYERS:
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  {k: v for k, v in ev.stats}))
    return out, sorted(spans, key=lambda s: s[1])


def _named(spans, name):
    return [s for s in spans if s[0] == name]


# ---------------------------------------------------------------------- #
# the helper
# ---------------------------------------------------------------------- #
def test_importing_telemetry_and_generating_load_no_jax():
    code = ("import sys\n"
            "import repro.telemetry\n"
            "from repro.workloads import get_scenario\n"
            "reqs = get_scenario('conversation-poisson').generate(10.0, 5.0)\n"
            "with repro.telemetry.span('workloads.x', n=1) as s:\n"
            "    s.set_metadata(m=2)\n"
            "assert reqs and 'jax' not in sys.modules, sorted(sys.modules)\n")
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env = dict(os.environ, PYTHONPATH=os.path.abspath(src))
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)


def test_phase_totals_its_entries_into_the_gauge():
    hub = TelemetryHub()
    phase = Phase("sim.x", hub, gauge="x")
    for _ in range(2):
        with phase:
            pass
    assert phase.wall_s > 0.0
    assert hub.gauge("phase/x_wall_s").value == phase.wall_s
    bare = Phase("sim.y")
    with bare:
        pass
    assert bare.wall_s > 0.0
    assert "phase/y_wall_s" not in hub.gauges


def test_span_names_start_with_a_layer(tmp_path):
    _, spans = _profile(tmp_path, lambda: [
        span(f"{layer}.probe", k=1).__enter__().__exit__(None, None, None)
        for layer in LAYERS])
    assert [s[0] for s in spans] == [f"{layer}.probe" for layer in LAYERS]
    assert all(s[3] == {"k": 1} for s in spans)


# ---------------------------------------------------------------------- #
# the fleet engine
# ---------------------------------------------------------------------- #
FLEET_CFG = ExperimentConfig(duration_s=8.0, seed=5).with_engine(
    "fleet", backend="jax", use_kernel=True)


@pytest.fixture(scope="module")
def fleet_profile(tmp_path_factory):
    hub = TelemetryHub()
    result, spans = _profile(
        tmp_path_factory.mktemp("fleet"),
        lambda: run_experiment(FLEET_CFG, telemetry=hub))
    return result, spans, hub


def test_fleet_spans_nest_under_the_experiment(fleet_profile):
    _, spans, _ = fleet_profile
    fleet = [s for s in spans if s[0].startswith("fleetsim.")]
    assert {s[0] for s in fleet} == FLEET_SPANS
    (_, e0, e1, stats), = _named(fleet, "fleetsim.experiment")
    assert stats == {"seed": FLEET_CFG.seed}
    assert all(e0 <= s <= e <= e1 for _, s, e, _ in fleet)
    # generation runs inside the experiment when it draws the trace
    (_, g0, g1, _), = _named(spans, "workloads.generate")
    assert e0 <= g0 <= g1 <= e1


def test_generate_span_counts_the_requests(fleet_profile):
    _, spans, _ = fleet_profile
    (_, _, _, stats), = _named(spans, "workloads.generate")
    want = get_scenario(FLEET_CFG.scenario).generate(
        rate_rps=FLEET_CFG.rate_rps, duration_s=FLEET_CFG.duration_s,
        seed=FLEET_CFG.seed)
    assert stats == {"requests": len(want)}


def test_bin_span_counts_the_requests_converted(fleet_profile, tmp_path):
    from repro.sim.fleetsim import run_fleet_experiment
    _, spans, _ = fleet_profile
    # run_experiment draws columns: nothing to convert
    (_, _, _, stats), = _named(spans, "fleetsim.bin")
    assert stats == {"converted": 0}
    # a Request list is turned into columns once, in the bin span
    reqs = get_scenario(FLEET_CFG.scenario).generate(
        rate_rps=FLEET_CFG.rate_rps, duration_s=FLEET_CFG.duration_s,
        seed=FLEET_CFG.seed)
    cfg = FLEET_CFG.with_engine("fleet", backend="numpy")
    _, spans = _profile(tmp_path,
                        lambda: run_fleet_experiment(cfg, requests=reqs))
    (_, _, _, stats), = _named(spans, "fleetsim.bin")
    assert stats == {"converted": len(reqs)} and len(reqs) > 0
    assert not _named(spans, "workloads.generate")


def test_compile_span_is_the_compile_gauge(fleet_profile):
    _, spans, hub = fleet_profile
    compiles = _named(spans, "fleetsim.compile")
    span_s = sum(e - s for _, s, e, _ in compiles) / 1e9
    gauge = hub.gauge("phase/jax_compile_wall_s").value
    assert gauge > 0.0
    assert span_s == pytest.approx(gauge, abs=5e-3)


def test_fleet_profile_leaves_results_and_events_as_they_were(
        fleet_profile):
    result, _, hub = fleet_profile
    off = run_experiment(FLEET_CFG, telemetry=TelemetryHub())
    a, b = result.scalars(), off.scalars()
    assert a.keys() == b.keys()
    for k in a:
        assert a[k] == b[k] or (a[k] != a[k] and b[k] != b[k]), k
    kinds = {e["kind"] for e in hub.events}
    assert "engine" in kinds and "phase" not in kinds


# ---------------------------------------------------------------------- #
# the event runner
# ---------------------------------------------------------------------- #
def test_event_runner_phases_are_spans_and_gauges(tmp_path):
    hub = TelemetryHub()
    cfg = ExperimentConfig(duration_s=4.0, rate_rps=30.0, seed=2)
    _, spans = _profile(tmp_path, lambda: run_experiment(cfg, telemetry=hub))
    phases = ("trace_gen", "cluster_build", "sim_run", "collect")
    assert [s[0] for s in spans if s[0].startswith("sim.")] == \
        [f"sim.{p}" for p in phases]
    for p in phases:
        (_, s, e, _), = _named(spans, f"sim.{p}")
        assert hub.gauge(f"phase/{p}_wall_s").value == pytest.approx(
            (e - s) / 1e9, abs=5e-3)
    assert all(e["kind"] != "phase" for e in hub.events)
    assert all(e.get("cat") != "phase" for e in
               chrome_trace(hub.events, t_end=cfg.duration_s)["traceEvents"])


# ---------------------------------------------------------------------- #
# the serving engine
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def engine_profile(tmp_path_factory):
    """Three requests of 2 tokens into a 2-slot engine on a fake clock
    that advances 0.1 s a step, so the third waits for a slot."""
    from repro.configs import get_smoke_config
    from repro.models import Model
    from repro.serving.engine import InferenceEngine
    model = Model(get_smoke_config("llama3-8b"))
    params = model.init(jax.random.key(0))
    hub = TelemetryHub()
    clock = [0.0]
    eng = InferenceEngine(model, params, max_batch=2, max_len=32,
                          policy="proposed", num_host_cores=4,
                          clock=lambda: clock[0], telemetry=hub)
    rng = np.random.default_rng(0)
    for _ in range(3):
        eng.submit(rng.integers(0, 999, 8).tolist(), 2)

    def serve():
        steps = 0
        while eng.pending or eng.active_mask.any():
            eng.step()
            clock[0] += 0.1
            steps += 1
        return steps
    steps, spans = _profile(tmp_path_factory.mktemp("engine"), serve)
    return steps, spans, hub


def test_admissions_carry_request_and_queue_wait(engine_profile):
    _, spans, hub = engine_profile
    admits = _named(spans, "engine.admit")
    assert len(admits) == 3
    assert sorted(s[3]["req_id"] for s in admits) == [0, 1, 2]
    waits = {s[3]["req_id"]: s[3]["wait_ms"] for s in admits}
    assert waits[0] == waits[1] == 0.0
    assert waits[2] > 0.0
    assert hub.series["engine/queue_wait_s"].count == 3
    # each admission holds its prefill and its cache splice
    for _, a0, a1, _ in admits:
        for name in ("engine.prefill", "engine.splice"):
            assert sum(a0 <= s <= e <= a1 for _, s, e, _ in
                       _named(spans, name)) == 1


def test_decode_spans_count_active_slots(engine_profile):
    steps, spans, hub = engine_profile
    decodes = _named(spans, "engine.decode")
    assert len(decodes) == steps == len(_named(spans, "engine.step"))
    assert all(s[3]["slots"] == 2 and 1 <= s[3]["active"] <= 2
               for s in decodes)
    assert hub.series["engine/active_slots"].count == steps
    # every child span lies inside a step
    st = [(s, e) for _, s, e, _ in _named(spans, "engine.step")]
    for name in ("engine.admit", "engine.decode", "engine.tokens",
                 "engine.core_manager"):
        assert _named(spans, name)
        assert all(any(s0 <= s <= e <= e0 for s0, e0 in st)
                   for _, s, e, _ in _named(spans, name)), name
