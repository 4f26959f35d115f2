"""Tests for the pluggable workload-scenario subsystem.

SCENARIO_GOLD pins a seeded fingerprint of every built-in scenario
(count, first request, last arrival, token sums) so any change to a
scenario's RNG draw sequence is caught; LEGACY_GOLD pins values captured
from the pre-subsystem `sim.trace.generate` (seed commit), which the
`conversation-poisson` scenario must reproduce bit-exactly.
"""
import dataclasses
import io

import numpy as np
import pytest

from repro.sim import ExperimentConfig, TaskIdAllocator, run_policy_sweep
from repro.workloads import (ReplayScenario, Request, RequestColumns,
                             Scenario, available_scenarios,
                             canonical_scenario_name, export_csv_str,
                             get_scenario, load_csv, mixes, register_scenario,
                             request_stats, splice, time_scale)
from repro.workloads.arrivals import MMPPArrivals, PoissonArrivals

# Fingerprint per scenario at (rate_rps=50, duration_s=30, seed=11):
# (n_requests, first arrival, first in/out tokens, last arrival,
#  sum inputs, sum outputs)
SCENARIO_GOLD = {
    "code-poisson": (1448, 0.004591848626348808, 6593, 39,
                     29.97896439939197, 3822024, 28488),
    "conversation-constant": (1500, 0.02, 1052, 498,
                              29.99999999999945, 2243274, 319643),
    "conversation-diurnal": (1501, 0.0028699053914680046, 2895, 84,
                             29.9623805389845, 2205115, 329333),
    "conversation-flashcrowd": (1543, 0.007323333942804692, 793, 83,
                                29.99532852923168, 2279307, 335831),
    "conversation-mmpp": (957, 0.041154948310679465, 662, 103,
                          29.99815345560464, 1383374, 208663),
    "conversation-poisson": (1448, 0.004591848626348808, 3247, 438,
                             29.97896439939197, 2081384, 301368),
    "longcontext-poisson": (1448, 0.004591848626348808, 13573, 796,
                            29.97896439939197, 10010453, 585101),
    "mixed-poisson": (1490, 0.004591848626348808, 2895, 84,
                      29.99486284581264, 2676684, 241250),
}

# Captured from the seed-commit sim.trace.generate(TraceConfig(
#   rate_rps=60, duration_s=20, seed=3)) — the bit-exactness contract.
LEGACY_GOLD = {
    "n": 1190,
    "first_arrival": 0.0018335802113006638,
    "first_in": 116,
    "first_out": 203,
    "sum_in": 1742936,
}


class TestRegistry:
    def test_at_least_six_builtins(self):
        assert len(available_scenarios()) >= 6
        assert "conversation-poisson" in available_scenarios()

    def test_roundtrip_every_registered_name(self):
        for name in available_scenarios():
            sc = get_scenario(name)
            assert sc.name == name
            reqs = sc.generate(rate_rps=30, duration_s=5, seed=0)
            assert reqs, name
            assert all(0 <= r.arrival_s < 5 for r in reqs)
            assert all(r.req_id == i for i, r in enumerate(reqs))

    def test_name_normalization(self):
        assert canonical_scenario_name("Conversation_Poisson") == \
            "conversation-poisson"
        a = get_scenario("conversation_poisson")
        b = get_scenario("CONVERSATION-POISSON")
        assert a.name == b.name == "conversation-poisson"

    def test_unknown_name_lists_available(self):
        with pytest.raises(KeyError, match="conversation-poisson"):
            get_scenario("definitely-not-a-scenario")

    def test_factory_opts_forwarded(self):
        sc = get_scenario("conversation-mmpp", burst_factor=12.0)
        reqs = sc.generate(rate_rps=40, duration_s=10, seed=0)
        assert reqs
        with pytest.raises(TypeError):
            get_scenario("conversation-poisson", bogus_opt=1)

    def test_custom_scenario_registers_and_runs(self):
        @register_scenario("test-tiny")
        def tiny() -> Scenario:
            return Scenario("test-tiny", mixes.CONVERSATION,
                            lambda rate, dur: PoissonArrivals(rate))

        try:
            reqs = get_scenario("test-tiny").generate(30, 5, 0)
            assert reqs == get_scenario(
                "conversation-poisson").generate(30, 5, 0)
        finally:
            from repro.workloads import registry
            registry._REGISTRY.pop("test-tiny", None)

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            @register_scenario("conversation-poisson")
            def imposter():
                pass


class TestSeededGoldenDeterminism:
    @pytest.mark.parametrize("name", sorted(SCENARIO_GOLD))
    def test_matches_pinned_fingerprint(self, name):
        reqs = get_scenario(name).generate(rate_rps=50, duration_s=30,
                                           seed=11)
        n, t0, in0, out0, t_last, sum_in, sum_out = SCENARIO_GOLD[name]
        assert len(reqs) == n
        assert reqs[0].arrival_s == t0
        assert (reqs[0].input_tokens, reqs[0].output_tokens) == (in0, out0)
        assert reqs[-1].arrival_s == t_last
        assert sum(r.input_tokens for r in reqs) == sum_in
        assert sum(r.output_tokens for r in reqs) == sum_out

    def test_every_builtin_covered(self):
        assert set(SCENARIO_GOLD) == set(available_scenarios())

    @pytest.mark.parametrize("name", sorted(SCENARIO_GOLD))
    def test_regenerate_equal(self, name):
        sc = get_scenario(name)
        assert (sc.generate(40, 10, seed=7)
                == get_scenario(name).generate(40, 10, seed=7))

    def test_seed_changes_stream(self):
        sc = get_scenario("conversation-poisson")
        assert sc.generate(40, 10, seed=0) != sc.generate(40, 10, seed=1)


def _oracle_sample(mix, rng):
    """One request's token counts as the per-request generator drew them
    before request generation filled columns: clipped with np.clip."""
    if isinstance(mix, mixes.BlendedMix):
        u = rng.random()
        for edge, (_, component) in zip(mix._cum, mix.components):
            if u <= edge:
                return _oracle_sample(component, rng)
        return _oracle_sample(mix.components[-1][1], rng)
    n_in = int(np.clip(rng.lognormal(mix.input_logmean, mix.input_logstd),
                       mix.input_min, mix.input_max))
    n_out = int(np.clip(rng.lognormal(mix.output_logmean,
                                      mix.output_logstd),
                        mix.output_min, mix.output_max))
    return n_in, n_out


def _oracle_trace(sc: Scenario, rate_rps, duration_s, seed):
    """A frozen copy of the per-request generation loop: one
    `default_rng(seed)`, a gap then the token counts for each request."""
    rng = np.random.default_rng(seed)
    process = sc.arrival_factory(rate_rps, duration_s)
    t, rows = 0.0, []
    while True:
        t += process.next_gap(rng, t)
        if t >= duration_s:
            break
        rows.append((t, *_oracle_sample(sc.mix, rng)))
    return rows


ORACLE_SEEDS = (0, 11, 2**31 + 977)


class TestRequestColumns:
    """`generate_columns` draws what the per-request loop drew, bit for
    bit, and `generate` is its `Request` list."""

    @pytest.mark.parametrize("seed", ORACLE_SEEDS)
    @pytest.mark.parametrize("name", sorted(SCENARIO_GOLD))
    def test_columns_match_per_request_oracle(self, name, seed):
        sc = get_scenario(name)
        cols = sc.generate_columns(rate_rps=50, duration_s=30, seed=seed)
        want = _oracle_trace(sc, 50, 30, seed)
        assert len(cols) == len(want) > 0
        assert cols.arrival_s.dtype == np.float64
        assert cols.input_tokens.dtype == cols.output_tokens.dtype \
            == np.int64
        t, n_in, n_out = (list(c) for c in zip(*want))
        assert cols.arrival_s.tolist() == t
        assert cols.input_tokens.tolist() == n_in
        assert cols.output_tokens.tolist() == n_out

    @pytest.mark.parametrize("seed", ORACLE_SEEDS)
    def test_clip_at_both_bounds_matches_np_clip(self, seed):
        """A mix whose bounds cut deep into both tails takes every
        branch of the clip on most requests."""
        tight = mixes.LognormalMix(6.93, 0.85, 4.92, 0.95, input_min=900,
                                   input_max=1100, output_min=100,
                                   output_max=160)
        sc = Scenario("tight", tight, lambda rate, dur: PoissonArrivals(rate))
        cols = sc.generate_columns(rate_rps=200, duration_s=10, seed=seed)
        want = _oracle_trace(sc, 200, 10, seed)
        n_in = [r[1] for r in want]
        assert {900, 1100} <= set(n_in)
        assert cols.input_tokens.tolist() == n_in
        assert cols.output_tokens.tolist() == [r[2] for r in want]

    @pytest.mark.parametrize("name", sorted(SCENARIO_GOLD))
    def test_generate_is_columns_as_requests(self, name):
        sc = get_scenario(name)
        cols = sc.generate_columns(40, 10, seed=7)
        reqs = sc.generate(40, 10, seed=7)
        assert reqs == cols.to_requests()
        assert [r.req_id for r in reqs] == list(range(len(reqs)))
        assert all(type(r.arrival_s) is float
                   and type(r.input_tokens) is int
                   and type(r.output_tokens) is int for r in reqs)
        back = RequestColumns.from_requests(reqs)
        for got, col in ((back.arrival_s, cols.arrival_s),
                         (back.input_tokens, cols.input_tokens),
                         (back.output_tokens, cols.output_tokens)):
            assert got.dtype == col.dtype
            assert np.array_equal(got, col)
        assert back.to_requests() == reqs

    def test_columns_are_read_only_and_one_length(self):
        cols = RequestColumns([0.5, 1.5], [10, 20], [1, 2])
        with pytest.raises(ValueError):
            cols.arrival_s[0] = 9.0
        assert len(cols) == 2
        assert not RequestColumns([], [], [])
        with pytest.raises(ValueError, match="one length"):
            RequestColumns([0.5], [10, 20], [1])


class TestLegacyBitExactness:
    def test_conversation_poisson_matches_seed_generator(self):
        reqs = get_scenario("conversation-poisson").generate(
            rate_rps=60, duration_s=20, seed=3)
        assert len(reqs) == LEGACY_GOLD["n"]
        assert reqs[0].arrival_s == LEGACY_GOLD["first_arrival"]
        assert reqs[0].input_tokens == LEGACY_GOLD["first_in"]
        assert reqs[0].output_tokens == LEGACY_GOLD["first_out"]
        assert sum(r.input_tokens for r in reqs) == LEGACY_GOLD["sum_in"]

    def test_traceconfig_shim_removed(self):
        """PR 2's deprecated TraceConfig/generate shims are gone; the
        bit-exactness contract lives on in
        `test_conversation_poisson_matches_seed_generator`."""
        with pytest.raises(ImportError):
            from repro.sim import TraceConfig  # noqa: F401


class TestMixStatistics:
    """Each token mix must match its published characterization."""

    def _sample(self, mix, n=20_000, seed=0):
        rng = np.random.default_rng(seed)
        pairs = [mix.sample_one(rng) for _ in range(n)]
        return (np.array([p[0] for p in pairs]),
                np.array([p[1] for p in pairs]))

    def test_conversation_matches_azure_characterization(self):
        """Splitwise Azure-conversation: input median ~1020 /
        mean ~1155, output mean ~211."""
        n_in, n_out = self._sample(mixes.CONVERSATION)
        assert 900 < np.median(n_in) < 1150
        assert n_in.mean() < 1600        # heavy tail, clipped at 8192
        assert 170 < n_out.mean() < 260

    def test_code_long_in_short_out(self):
        """Splitwise Azure-code: ~2k-token prompts, tiny completions."""
        n_in, n_out = self._sample(mixes.CODE)
        assert 1600 < np.median(n_in) < 2400
        assert np.median(n_out) < 50
        assert n_out.mean() < 60

    def test_long_context_document_scale(self):
        n_in, n_out = self._sample(mixes.LONG_CONTEXT)
        assert np.median(n_in) > 4000
        assert 150 < np.median(n_out) < 600

    def test_blended_between_components(self):
        n_in, n_out = self._sample(mixes.BLENDED)
        conv_in, _ = self._sample(mixes.CONVERSATION)
        code_out_med = np.median(self._sample(mixes.CODE)[1])
        # blend median input sits above pure conversation (code pulls up)
        assert np.median(n_in) > np.median(conv_in)
        # and blend output median sits above pure code
        assert np.median(n_out) > code_out_med

    def test_mean_rate_preserved_across_arrival_shapes(self):
        """Temporal scenarios modulate *around* rate_rps, they don't
        change delivered volume (long-horizon check)."""
        for name in ("conversation-diurnal", "conversation-mmpp",
                     "conversation-flashcrowd", "conversation-constant"):
            reqs = get_scenario(name).generate(rate_rps=50,
                                               duration_s=600, seed=4)
            rate = len(reqs) / 600.0
            assert rate == pytest.approx(50.0, rel=0.15), name

    def test_flashcrowd_overhanging_spike_keeps_mean_rate(self):
        """A spike window extending past the trace end must still
        normalize to the configured mean rate (overlap-aware)."""
        sc = get_scenario("conversation-flashcrowd",
                          spike_start_frac=0.95, spike_duration_frac=0.2)
        rates = [len(sc.generate(40, 100, seed=s)) / 100
                 for s in range(10)]
        assert np.mean(rates) == pytest.approx(40.0, rel=0.1)

    def test_diurnal_swings_within_a_trace(self):
        """Default period is one cycle per trace, so the day/night swing
        is visible at benchmark durations (phase=0: peak first half)."""
        reqs = get_scenario("conversation-diurnal").generate(50, 300,
                                                             seed=2)
        ts = np.array([r.arrival_s for r in reqs])
        assert (ts < 150).sum() > 1.5 * (ts >= 150).sum()

    def test_mmpp_burstier_than_poisson(self):
        """Index of dispersion of per-second counts: MMPP >> Poisson."""

        def dispersion(name):
            reqs = get_scenario(name).generate(50, 300, seed=9)
            counts = np.bincount(
                np.array([int(r.arrival_s) for r in reqs]), minlength=300)
            return counts.var() / counts.mean()

        assert dispersion("conversation-mmpp") > \
            3 * dispersion("conversation-poisson")


class TestTraceIO:
    def _mk(self, n=50, seed=0):
        return get_scenario("conversation-poisson").generate(30, 10, seed)

    def test_csv_roundtrip_equality(self):
        reqs = self._mk()
        text = export_csv_str(reqs)
        back = load_csv(io.StringIO(text))
        assert back == reqs

    def test_export_every_scenario_roundtrips(self):
        for name in available_scenarios():
            reqs = get_scenario(name).generate(30, 5, seed=2)
            back = load_csv(io.StringIO(export_csv_str(reqs)))
            assert back == reqs, name

    def test_load_requires_azure_schema(self):
        with pytest.raises(ValueError, match="ContextTokens"):
            load_csv(io.StringIO("time,in,out\n1,2,3\n"))

    def test_load_sorts_and_renumbers(self):
        text = ("TIMESTAMP,ContextTokens,GeneratedTokens\n"
                "5.0,100,10\n2.0,200,20\n9.0,300,30\n")
        reqs = load_csv(io.StringIO(text))
        assert [r.req_id for r in reqs] == [0, 1, 2]
        # relative float timestamps pass through un-rebased...
        assert [r.arrival_s for r in reqs] == [2.0, 5.0, 9.0]
        assert [r.input_tokens for r in reqs] == [200, 100, 300]
        # ...unless rebasing is forced
        rebased = load_csv(io.StringIO(text), rebase=True)
        assert [r.arrival_s for r in rebased] == [0.0, 3.0, 7.0]

    def test_load_accepts_iso_timestamps(self):
        text = ("TIMESTAMP,ContextTokens,GeneratedTokens\n"
                "2024-05-01 00:00:00,100,10\n"
                "2024-05-01 00:00:30,200,20\n")
        reqs = load_csv(io.StringIO(text))
        assert [r.arrival_s for r in reqs] == [0.0, 30.0]

    def test_load_accepts_azure_seven_digit_fractions(self):
        """The real Azure trace carries 7 fractional digits, which
        Python 3.10's fromisoformat rejects unnormalized."""
        text = ("TIMESTAMP,ContextTokens,GeneratedTokens\n"
                "2023-11-16 18:15:46.6805900,100,10\n"
                "2023-11-16 18:15:47.1805901,200,20\n")
        reqs = load_csv(io.StringIO(text))
        assert reqs[0].arrival_s == 0.0
        assert reqs[1].arrival_s == pytest.approx(0.5, abs=1e-6)

    def test_load_rejects_mixed_timestamp_kinds(self):
        """One absolute datetime among relative floats would rebase the
        floats into garbage — refuse instead."""
        text = ("TIMESTAMP,ContextTokens,GeneratedTokens\n"
                "0.5,100,10\n2024-05-01 00:00:00,200,20\n")
        with pytest.raises(ValueError, match="mixes"):
            load_csv(io.StringIO(text))

    def test_splice_window(self):
        reqs = self._mk()
        window = splice(reqs, start_s=2.0, stop_s=6.0)
        assert window
        assert all(0 <= r.arrival_s < 4.0 for r in window)
        assert [r.req_id for r in window] == list(range(len(window)))

    def test_time_scale_changes_rate(self):
        reqs = self._mk()
        fast = time_scale(reqs, 0.5)
        assert max(r.arrival_s for r in fast) == pytest.approx(
            0.5 * max(r.arrival_s for r in reqs))
        with pytest.raises(ValueError):
            time_scale(reqs, 0.0)

    def test_replay_scenario_rescales_and_truncates(self):
        source = get_scenario("conversation-poisson").generate(20, 60, 5)
        sc = ReplayScenario.from_requests(source, name="azure-conv")
        out = sc.generate(rate_rps=40, duration_s=10, seed=999)
        assert out == sc.generate(rate_rps=40, duration_s=10, seed=0)
        assert all(r.arrival_s < 10 for r in out)
        rate = len(out) / 10.0
        assert rate == pytest.approx(40.0, rel=0.25)
        # token counts come from the recorded trace, untouched
        assert {(r.input_tokens, r.output_tokens) for r in out} <= \
            {(r.input_tokens, r.output_tokens) for r in source}

    def test_replay_loops_to_fill_requested_duration(self):
        """A short recording must cover duration_s (the scenario
        contract), looping end-to-end; loop=False emits it once."""
        source = get_scenario("conversation-poisson").generate(20, 60, 5)
        looped = ReplayScenario.from_requests(source).generate(
            rate_rps=60, duration_s=120)
        assert max(r.arrival_s for r in looped) > 100
        assert len(looped) / 120 == pytest.approx(60.0, rel=0.05)
        assert [r.req_id for r in looped] == list(range(len(looped)))
        once = ReplayScenario.from_requests(source, loop=False).generate(
            rate_rps=60, duration_s=120)
        assert len(once) == len(source)
        assert max(r.arrival_s for r in once) < 25

    def test_replay_degenerate_window_does_not_crash(self):
        """A spliced window with one request (or identical timestamps)
        has zero span: replay it at t=0 instead of raising."""
        source = [Request(0, 5.0, 100, 10), Request(1, 5.0, 200, 20)]
        sc = ReplayScenario.from_requests(source, start_s=5.0, stop_s=6.0)
        out = sc.generate(rate_rps=40, duration_s=10, seed=0)
        assert [r.arrival_s for r in out] == [0.0, 0.0]
        single = ReplayScenario.from_requests([Request(0, 5.0, 100, 10)],
                                              loop=False)
        assert len(single.generate(rate_rps=40, duration_s=10)) == 1

    def test_replay_from_csv_file(self, tmp_path):
        from repro.workloads import export_csv
        source = self._mk()
        path = tmp_path / "azure_conv.csv"
        export_csv(source, path)
        sc = ReplayScenario.from_csv(path)
        assert sc.name == "azure_conv"
        assert tuple(sc.requests) == tuple(source)


class TestRequestStats:
    def test_empty_stream_returns_zero_dict(self):
        stats = request_stats([])
        assert stats["n_requests"] == 0
        assert all(v == 0 for v in stats.values())
        assert not any(np.isnan(v) for v in stats.values())

    def test_trace_stats_shim_removed(self):
        with pytest.raises(ImportError):
            from repro.sim import trace_stats  # noqa: F401

    def test_basic_stats(self):
        reqs = [Request(0, 1.0, 100, 10), Request(1, 2.0, 300, 30)]
        stats = request_stats(reqs)
        assert stats["n_requests"] == 2
        assert stats["input_mean"] == 200.0
        assert stats["output_median"] == 20.0
        assert stats["mean_rate_rps"] == pytest.approx(1.0)


class TestExperimentIntegration:
    def test_config_normalizes_and_hashes(self):
        a = ExperimentConfig(scenario="Conversation_MMPP",
                             scenario_opts={"burst_factor": 8.0})
        b = ExperimentConfig(scenario="conversation-mmpp",
                             scenario_opts=(("burst_factor", 8.0),))
        assert a == b and hash(a) == hash(b)
        assert a.scenario_options == {"burst_factor": 8.0}
        assert a.with_scenario("code-poisson").scenario_opts == ()

    def test_policy_scenario_grid_sweep(self):
        cfg = ExperimentConfig(num_cores=40, rate_rps=40, duration_s=5,
                               seed=3)
        grid = run_policy_sweep(cfg, policies=("linux", "proposed"),
                                scenarios=("conversation-poisson",
                                           "conversation-mmpp"))
        assert set(grid) == {(p, s)
                             for p in ("linux", "proposed")
                             for s in ("conversation-poisson",
                                       "conversation-mmpp")}
        for (p, s), m in grid.items():
            assert m.policy == p and m.scenario == s
            assert m.completed >= 0

    def test_grid_entry_matches_single_run(self):
        from repro.sim import run_experiment
        cfg = ExperimentConfig(rate_rps=40, duration_s=5, seed=3,
                               scenario="conversation-mmpp")
        single = run_experiment(cfg)
        grid = run_policy_sweep(cfg, policies=("proposed",),
                                scenarios=("conversation-mmpp",))
        m = grid[("proposed", "conversation-mmpp")]
        assert m.freq_cv_percentiles == single.freq_cv_percentiles
        assert m.completed == single.completed


class TestTaskIdAllocation:
    def test_per_allocator_monotone_independent(self):
        a, b = TaskIdAllocator(), TaskIdAllocator()
        ids_a = [a.next_id() for _ in range(5)]
        ids_b = [b.next_id() for _ in range(3)]
        assert ids_a == [0, 1, 2, 3, 4]
        assert ids_b == [0, 1, 2]           # no cross-allocator bleed

    def test_interleaved_clusters_get_independent_ids(self):
        """Two clusters built side by side (concurrent experiments) must
        both start their task-id streams at 0."""
        from repro.sim import Cluster
        cfg = ExperimentConfig(rate_rps=40, duration_s=2, seed=0)
        c1, c2 = Cluster(cfg), Cluster(cfg)
        t1 = c1.machines[0].task_ids.new("submit")
        t2 = c2.machines[0].task_ids.new("submit")
        assert t1.task_id == 0 and t2.task_id == 0

    def test_cluster_machines_share_one_stream(self):
        from repro.sim import Cluster
        cfg = ExperimentConfig(rate_rps=40, duration_s=2, seed=0)
        c = Cluster(cfg)
        ids = [c.machines[i].task_ids.next_id() for i in range(4)]
        assert ids == [0, 1, 2, 3]
