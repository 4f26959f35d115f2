"""Tests for fleet-batched aging settlement (`repro.sim.fleetstate`)."""
import numpy as np
import pytest

from repro.core import CoreManager
from repro.sim.fleetstate import FleetAgingSettler, settle_fleet


def build_fleet(n_machines=6, num_cores=8, policy="proposed"):
    """Managers with heterogeneous per-core regimes (busy / idle / gated)."""
    ms = [CoreManager(num_cores, policy=policy,
                      rng=np.random.default_rng(100 + i))
          for i in range(n_machines)]
    tid = 0
    for i, m in enumerate(ms):
        for _ in range(i % (num_cores // 2 + 1)):
            m.assign(tid, 0.1 * i)
            tid += 1
        if i % 2:
            m.periodic(0.5)          # proposed gates spare cores
    return ms


class TestNumpyBackendBitExact:
    def test_matches_sequential_settle_all(self):
        """The stacked advance must reproduce per-machine settle_all
        bit-for-bit — the serial numpy path stays golden-exact."""
        a = build_fleet()
        b = build_fleet()
        for k in range(1, 6):
            now = 7.3 * k
            for m in a:
                m.settle_all(now)
            FleetAgingSettler(b).settle(now)
            for ma, mb in zip(a, b):
                np.testing.assert_array_equal(ma.dvth, mb.dvth)
                np.testing.assert_array_equal(ma.last_update,
                                              mb.last_update)
                assert ma.now == mb.now

    def test_noop_when_already_settled(self):
        ms = build_fleet(n_machines=2)
        s = FleetAgingSettler(ms)
        s.settle(5.0)
        before = [m.dvth.copy() for m in ms]
        s.settle(5.0)                 # no elapsed time anywhere
        for m, d in zip(ms, before):
            np.testing.assert_array_equal(m.dvth, d)
            assert m.now == 5.0

    def test_settle_fleet_wrapper(self):
        ms = build_fleet(n_machines=2)
        settle_fleet(ms, 3.0)
        assert all(m.now == 3.0 for m in ms)
        assert all((m.last_update == 3.0).all() for m in ms)


class TestValidation:
    def test_rejects_heterogeneous_core_counts(self):
        ms = [CoreManager(4, rng=np.random.default_rng(0)),
              CoreManager(8, rng=np.random.default_rng(1))]
        with pytest.raises(ValueError, match="homogeneous"):
            FleetAgingSettler(ms)

    def test_rejects_heterogeneous_params(self):
        import dataclasses
        from repro.core import aging
        p2 = aging.solve_k(dataclasses.replace(aging.DEFAULT_PARAMS,
                                               E0=0.25))
        ms = [CoreManager(4, rng=np.random.default_rng(0)),
              CoreManager(4, aging_params=p2,
                          rng=np.random.default_rng(1))]
        with pytest.raises(ValueError, match="homogeneous"):
            FleetAgingSettler(ms)

    def test_rejects_empty_and_bad_backend(self):
        with pytest.raises(ValueError, match="at least one"):
            FleetAgingSettler([])
        with pytest.raises(ValueError, match="backend"):
            FleetAgingSettler([CoreManager(4)], backend="tpu-magic")

    def test_auto_backend_resolves(self):
        s = FleetAgingSettler([CoreManager(4)], backend="auto")
        assert s.backend == "jax"          # no numpy fallback
        assert s.interpret                 # the kernel, off-TPU


class TestJaxBackend:
    def test_jax_matches_numpy_within_float32(self):
        """The Pallas-kernel path is float32: same physics to ~1e-6,
        explicitly not bit-exact (which is why the Cluster default
        stays numpy)."""
        pytest.importorskip("jax")
        a = build_fleet(n_machines=3, num_cores=8)
        b = build_fleet(n_machines=3, num_cores=8)
        FleetAgingSettler(a, backend="numpy").settle(11.0)
        FleetAgingSettler(b, backend="jax").settle(11.0)
        for ma, mb in zip(a, b):
            np.testing.assert_allclose(ma.dvth, mb.dvth,
                                       rtol=2e-6, atol=1e-8)


class TestClusterIntegration:
    def test_cluster_uses_batched_settler(self):
        from repro.sim import Cluster, ExperimentConfig
        c = Cluster(ExperimentConfig())
        assert c.fleet_settler.backend == "numpy"
        assert len(c.fleet_settler.managers) == 22
