"""Fleet-batched NBTI aging settlement.

The cluster's periodic tick used to settle each machine's cores through
its own `CoreManager.settle_all` — 22 sequential numpy dispatch chains
per second of simulated time. `FleetAgingSettler` stacks every
machine's per-core state into one `(n_machines, n_cores)` batch and
advances all of it through a single `advance_dvth` call, then scatters
the settled shifts back into the managers.

Backends:

  numpy  — default; bit-identical to calling `settle_all` per machine
           (elementwise float64 math over a stacked array; pinned by
           tests/test_fleetstate.py), so the serial simulation stays
           golden-exact.
  jax    — routes the stacked batch through the fleet-scale Pallas
           kernel (`repro.kernels.aging_update`, float32; compiled on
           TPU, interpret mode elsewhere, resolved once in the
           constructor as `interpret`). NOT bit-exact with the float64
           numpy path — for analytics sweeps and kernel-backed scale
           runs, not for golden-pinned experiments.
  auto   — the jax backend.

Managers must be homogeneous (same `AgingParams`, same core count) —
exactly what a `Cluster` builds.
"""
from __future__ import annotations

import numpy as np

from repro.core import aging, temperature

_BACKENDS = ("numpy", "jax", "auto")


class FleetAgingSettler:
    """Settles a fleet of `CoreManager`s to a common timestamp in one
    batched dVth advance (the paper's hot loop, fleet-vectorized)."""

    def __init__(self, managers, backend: str = "numpy"):
        if backend not in _BACKENDS:
            raise ValueError(f"unknown fleetstate backend {backend!r}; "
                             f"expected one of {_BACKENDS}")
        managers = list(managers)
        if not managers:
            raise ValueError("FleetAgingSettler needs at least one manager")
        params = managers[0].params
        n = managers[0].num_cores
        for m in managers[1:]:
            if m.params != params or m.num_cores != n:
                raise ValueError(
                    "FleetAgingSettler requires homogeneous managers "
                    "(same AgingParams and num_cores)")
        self.managers = managers
        self.params = params
        self.num_cores = n
        self.backend = "jax" if backend == "auto" else backend
        self.interpret = False
        if self.backend == "jax":
            import jax
            self.interpret = jax.default_backend() != "tpu"

    # ------------------------------------------------------------------ #
    def _gather(self, now: float):
        """Stack per-machine state into (M, N) regime arrays (regimes
        derived through the same `temperature.regime_arrays` helper the
        per-machine settle path uses, so the two can never drift)."""
        ms = self.managers
        dvth = np.stack([m.dvth for m in ms])
        tau = now - np.stack([m.last_update for m in ms])
        cs = np.stack([m.c_state for m in ms])
        alloc = np.stack([m.task_of_core for m in ms]) >= 0
        temps, stress = temperature.regime_arrays(cs, alloc)
        return dvth, temps, stress, np.maximum(tau, 0.0)

    def _scatter(self, new_dvth: np.ndarray, now: float) -> None:
        for k, m in enumerate(self.managers):
            m.dvth[:] = new_dvth[k]
            np.maximum(m.last_update, now, out=m.last_update)
            if now > m.now:
                m.now = now

    # ------------------------------------------------------------------ #
    def settle(self, now: float) -> None:
        """Advance every machine's every core to `now` under its current
        regime. Equivalent to `for m in managers: m.settle_all(now)`
        (bit-identical on the numpy backend), one batched call."""
        dvth, temps, stress, tau = self._gather(now)
        if not (tau > 0.0).any():
            for m in self.managers:
                if now > m.now:
                    m.now = now
            return
        if self.backend == "jax":
            new = self._advance_jax(dvth, temps, stress, tau)
        else:
            adf_vals = aging.adf(self.params, temps, stress)
            new = aging.advance_dvth(self.params, dvth, adf_vals, tau)
        self._scatter(new, now)

    def _advance_jax(self, dvth, temps, stress, tau) -> np.ndarray:
        """Flatten the (M, N) batch through the Pallas fleet kernel
        (float32; the kernel pads to its 128-lane block size)."""
        from repro.kernels.aging_update.ops import advance_fleet

        shape = dvth.shape
        out = advance_fleet(dvth.ravel(), temps.ravel(), stress.ravel(),
                            tau.ravel(), self.params,
                            interpret=self.interpret)
        return np.asarray(out, dtype=np.float64).reshape(shape)


class GroupedAgingSettler:
    """Heterogeneous-fleet settler: groups managers by `(AgingParams,
    num_cores)` and runs one `FleetAgingSettler` per homogeneous group.

    Mixed fleets (`repro.hardware`) build machines with per-SKU core
    counts and aging parameters, so one stacked batch no longer fits;
    each group still advances through a single batched call, and every
    group is bit-identical to its machines settling individually.
    """

    def __init__(self, managers, backend: str = "numpy"):
        managers = list(managers)
        if not managers:
            raise ValueError("GroupedAgingSettler needs at least one "
                             "manager")
        groups: dict[tuple, list] = {}
        for m in managers:
            groups.setdefault((m.params, m.num_cores), []).append(m)
        self.settlers = [FleetAgingSettler(g, backend=backend)
                         for g in groups.values()]
        self.managers = managers
        # all groups resolve "auto" identically; surface the first
        self.backend = self.settlers[0].backend

    def settle(self, now: float) -> None:
        for s in self.settlers:
            s.settle(now)


def settle_fleet(managers, now: float, backend: str = "numpy") -> None:
    """One-shot convenience wrapper around `FleetAgingSettler`."""
    FleetAgingSettler(managers, backend=backend).settle(now)
