"""Vectorized time-stepped fleet engine (`ExperimentConfig.engine="fleet"`).

The event engine (`repro.sim.cluster`) is the bit-exact small-scale
reference: every CPU task is a heap event, every core an object field.
That tops out around tens of machines x minutes. This module is the
second engine of the two-engine architecture: a mean-field / fluid
surrogate that advances the whole stacked ``(n_machines, n_cores)``
fleet state with array ops, so hundreds of machines x hours-to-weeks of
simulated time run at interactive wall times.

Model (per micro step of ``dt_s`` seconds, all quantities fluid):

* **Workload** — the scenario's request trace is binned into per-step
  arrival counts / input-token / output-token sums. Arrivals split
  evenly across prompt instances (the fluid limit of JSQ: a
  join-shortest-queue router keeps fluid queues balanced, so the
  even split *is* its mean-field fixed point).
* **Prefill** — each prompt machine carries a GPU backlog in seconds +
  requests; it drains at 1 GPU-second/second using the event engine's
  timing constants. Completed prefills flow (evenly, same JSQ limit) to
  token instances.
* **Decode** — each token machine carries a continuous batch (capped at
  ``MAX_DECODE_BATCH``) and its remaining-token mass; the iteration
  period is the event engine's ``start_iteration`` CPU time plus the
  batch-dependent GPU pass, so CPU aging genuinely stretches decode.
* **CPU** — per-request task work (the same ``TASK_DURATIONS_S``
  constants the event engine schedules as discrete events) arrives as a
  per-machine fluid inflow; busy cores follow Little's law
  (work rate / settled core speed), with overflow carried as an
  oversubscription backlog.
* **Aging** — once per idling period the accumulated busy core-seconds
  are settled through the exact NBTI recursion (the update composes
  exactly under a constant ADF, so per-period advancement introduces no
  integration error beyond regime-ordering within the period), and
  Algorithm 2's reaction function gates most-aged / wakes least-aged
  cores per machine via vectorized rank selection.

One macro step (``_macro_step``, written against an array namespace
``xp``) and one state layout serve two drivers, which advance in chunks
of macro steps between checkpoints:

* ``backend="numpy"`` — float64, from a Python loop; deterministic, and
  the reference for checkpoint/resume exactness (a resumed run
  reproduces the uninterrupted run's ``ExperimentResult`` scalars
  bit-for-bit).
* ``backend="jax"`` — float32, as the body of the jitted ``lax.scan``
  ``fleet_scan`` (an inner scan covers the micro steps); the aging
  settlement routes through ``repro.kernels.aging_update`` — the
  compiled Pallas kernel on TPU, its jnp oracle elsewhere (the kernel in
  interpret mode when ``use_kernel=True`` asks for it off-TPU). The
  engine resolves this once (``FleetEngine.use_kernel`` /
  ``.interpret``) and reports it in the telemetry ``engine`` event.
  Fast, NOT bit-exact vs numpy.

``backend="auto"`` is the jax backend (jax is a hard dependency).

What the surrogate does NOT model: per-core task placement (stress
spreads evenly over the active set, so within-machine frequency CV
comes from process variation + gating asymmetry only), router choice
(always the JSQ fluid limit), and sub-period event ordering. Parity vs
the event engine on small configs is pinned with tolerances in
``tests/test_fleetsim.py``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import numpy as np

from repro.core import aging, temperature, variation
from repro.hardware.inventory import resolve_fleet
from repro.power.residency import StateResidency
from repro.sim import metrics as metrics_mod
from repro.sim.cluster import (
    DECODE_ITER_BASE_S,
    DECODE_ITER_PER_REQ_S,
    IB_LINK_BW_BPS,
    KV_BYTES_PER_TOKEN,
    MAX_DECODE_BATCH,
    PREFILL_BASE_S,
    PREFILL_PER_TOKEN_S,
)
from repro.sim.config import ExperimentConfig
from repro.sim.results import ExperimentResult
from repro.sim.tasks import TASK_DURATIONS_S
from repro.telemetry.spans import Phase, span
from repro.workloads.base import RequestColumns

# ---------------------------------------------------------------------- #
# Per-request CPU work (nominal core-seconds), assembled from the same
# task table the event engine schedules discretely (see Machine.*).
# ---------------------------------------------------------------------- #
# prompt side, on arrival: submit -> submit_chain -> alloc_memory ->
# submit_task (the serial admission chain).
_W_PROMPT_ARRIVAL = (TASK_DURATIONS_S["submit"]
                     + TASK_DURATIONS_S["submit_chain"]
                     + TASK_DURATIONS_S["alloc_memory"]
                     + TASK_DURATIONS_S["submit_task"])
# prompt side, on prefill completion: finish_task || submit_flow.
_W_PROMPT_FINISH = (TASK_DURATIONS_S["finish_task"]
                    + TASK_DURATIONS_S["submit_flow"])
# token side, on flow arrival: flow_completion -> alloc_memory.
_W_TOKEN_ARRIVAL = (TASK_DURATIONS_S["flow_completion"]
                    + TASK_DURATIONS_S["alloc_memory"])
# token side, per decode iteration (serial with the GPU pass).
_W_TOKEN_ITER = TASK_DURATIONS_S["start_iteration"]
# token side, on request completion: free_memory + finish_request
# (after t_done — not on the latency critical path, but CPU load).
_W_TOKEN_FINISH = (TASK_DURATIONS_S["free_memory"]
                   + TASK_DURATIONS_S["finish_request"])
# serial CPU latency before prefill admission (excludes submit_task,
# which is folded into the prefill service time like the event loop).
_LAT_CPU_PROMPT = (TASK_DURATIONS_S["submit"]
                   + TASK_DURATIONS_S["submit_chain"]
                   + TASK_DURATIONS_S["alloc_memory"])
_MEAN_TASK_S = float(np.mean(list(TASK_DURATIONS_S.values())))
_KV_S_PER_TOKEN = KV_BYTES_PER_TOKEN / IB_LINK_BW_BPS

_IDLE_BINS = 512          # linear histogram over [-1, 1] for idle_norm
_EPS = 1e-12
# Rows of the stacked window sums: `mw` (metrics windows) sums the
# micro-step observables of `_micro_step` by these names ("cnt" counts
# the steps); `res` (residency windows) sums per-machine core-seconds.
_MW_ROWS = ("cnt", "wait", "iter", "cpuw", "sp", "st", "comps")
_RES_ROWS = ("busy", "idle", "gated", "fbusy")
# the fluid queue columns, in `_micro_step`'s queue-tuple order
_QUEUE_KEYS = ("pq_s", "pq_n", "pq_out", "d_batch", "d_tokens", "d_pend",
               "d_pend_tok", "cpu_backlog")


def _float_columns(requests: RequestColumns):
    """(arrival_s, input_tokens, output_tokens) of a trace, all float64."""
    return (requests.arrival_s,
            requests.input_tokens.astype(np.float64),
            requests.output_tokens.astype(np.float64))


def _resolve_backend(requested: str) -> str:
    """'auto' is the jax backend; 'numpy' the float64 reference."""
    if requested in ("numpy", "jax"):
        return requested
    if requested == "auto":
        return "jax"
    raise ValueError(f"unknown fleet backend {requested!r}: expected "
                     f"'numpy', 'jax' or 'auto'")


@dataclasses.dataclass
class _Shape:
    """Static geometry + timing constants of one fleet run."""
    n_prompt: int
    n_token: int
    num_cores: int
    dt_s: float
    steps_per_period: int     # micro steps per idling period
    n_macro: int              # macro (idling-period) steps
    mwin_s: float             # metrics-window width
    n_mwin: int
    pwin_s: float             # residency-window width
    n_pwin: int
    duration_s: float
    mean_out_tokens: float    # trace-wide mean output tokens/request
    gating: bool              # policy gates cores (Algorithm 2)?
    # per-machine CPU-wait clip fed into the window means: inf (exact
    # no-op) when faultless; duration_s under fault injection, so one
    # dead machine's unbounded backlog/capacity ratio can't poison the
    # fleet-mean latency windows
    wait_cap_s: float = float("inf")
    # per-machine core counts for heterogeneous fleets
    # (`repro.hardware`): None = uniform legacy fleet (`num_cores`
    # everywhere, zero ragged bookkeeping); otherwise a fleet-order
    # tuple and `num_cores` is the padded max — lanes beyond a
    # machine's count are excluded everywhere via a pad mask.
    core_counts: tuple | None = None

    @property
    def n_machines(self) -> int:
        return self.n_prompt + self.n_token

    @property
    def total_cores(self) -> int:
        if self.core_counts is None:
            return self.n_machines * self.num_cores
        return int(sum(self.core_counts))


def _initial_state(shape: _Shape) -> dict[str, np.ndarray]:
    """Stacked fleet state; every mutable quantity of a run lives here
    (and therefore checkpoints/restores as one array dict)."""
    M, N = shape.n_machines, shape.num_cores
    P, K = shape.n_prompt, shape.n_token
    W, PW = shape.n_mwin, shape.n_pwin
    return {
        "macro": np.zeros((), dtype=np.int64),       # completed macro steps
        "dvth": np.zeros((M, N)),
        "gated": np.zeros((M, N), dtype=bool),
        # fluid queues
        "pq_s": np.zeros(P), "pq_n": np.zeros(P), "pq_out": np.zeros(P),
        "d_batch": np.zeros(K), "d_tokens": np.zeros(K),
        "d_pend": np.zeros(K), "d_pend_tok": np.zeros(K),
        "cpu_backlog": np.zeros(M),
        "busy_s": np.zeros((M, N)),     # busy core-seconds since settle
        "u_last": np.zeros(M), "ov_last": np.zeros(M),
        # metrics windows (streaming aggregates — bounded for any
        # horizon) and per-machine residency windows (for the power
        # models), rows named by _MW_ROWS / _RES_ROWS
        "mw": np.zeros((len(_MW_ROWS), W)),
        "res": np.zeros((len(_RES_ROWS), M, PW)),
        # sample statistics
        "idle_hist": np.zeros(_IDLE_BINS, dtype=np.int64),
        "task_sum": np.zeros(()), "task_cnt": np.zeros(()),
        "task_max": np.zeros(()),
        "completions": np.zeros(()),
    }


# ---------------------------------------------------------------------- #
# Functional fleet step — written once against an array namespace `xp`
# (numpy or jax.numpy) so both backends run the same physics.
# ---------------------------------------------------------------------- #
def _micro_step(xp, shape: _Shape, dyn, q, arr_row):
    """One fluid micro step. `dyn` = per-period derived state
    (sp, st, sm, active counts); `q` = queue-state tuple; `arr_row` =
    (arrivals, input-token sum, output-token sum) for this step.
    Returns (q', observables)."""
    (pq_s, pq_n, pq_out, d_batch, d_tokens, d_pend, d_pend_tok,
     cpu_backlog) = q
    sp, st, sm, active = dyn          # (P,), (K,), (M,), (M,)
    P, K = shape.n_prompt, shape.n_token
    dt = shape.dt_s
    a, in_sum, out_sum = arr_row

    # Prefill wait seen by an arrival this step: GPU backlog ahead of it
    # (sampled before the arrival joins), fleet-mean across prompt
    # instances (even JSQ-limit split).
    wait_p = xp.mean(pq_s)

    # 1) arrivals -> prompt queues (even split) + prompt CPU work
    pq_n = pq_n + a / P
    pq_s = pq_s + (a * PREFILL_BASE_S + PREFILL_PER_TOKEN_S * in_sum
                   + a * TASK_DURATIONS_S["submit_task"] / sp) / P
    pq_out = pq_out + out_sum / P
    work_p = (a / P) * _W_PROMPT_ARRIVAL

    # 2) prefill drain (1 GPU-second per second)
    ds = xp.minimum(pq_s, dt)
    frac = ds / xp.maximum(pq_s, _EPS)
    done_n = pq_n * frac
    done_out = pq_out * frac
    pq_s = pq_s - ds
    pq_n = pq_n - done_n
    pq_out = pq_out - done_out
    work_p = work_p + done_n * _W_PROMPT_FINISH
    c_total = xp.sum(done_n)
    o_total = xp.sum(done_out)

    # 3) flow to token instances (even split) + decode admission
    d_pend = d_pend + c_total / K
    d_pend_tok = d_pend_tok + o_total / K
    work_t = (c_total / K) * _W_TOKEN_ARRIVAL
    room = xp.maximum(MAX_DECODE_BATCH - d_batch, 0.0)
    adm = xp.minimum(d_pend, room)
    tok_per_pend = d_pend_tok / xp.maximum(d_pend, _EPS)
    d_batch = d_batch + adm
    d_tokens = d_tokens + adm * tok_per_pend
    d_pend = d_pend - adm
    d_pend_tok = d_pend_tok - adm * tok_per_pend

    # 4) decode iterations: CPU start_iteration is serial with the GPU
    # pass, so aged (slower) CPUs stretch the iteration period — the
    # paper's aging -> service-quality coupling.
    iter_period = (_W_TOKEN_ITER / st + DECODE_ITER_BASE_S
                   + DECODE_ITER_PER_REQ_S
                   * xp.minimum(d_batch, MAX_DECODE_BATCH))
    busy_gpu = d_batch > _EPS
    iters = xp.where(busy_gpu, dt / iter_period, 0.0)
    tokens_out = xp.minimum(iters * d_batch, d_tokens)
    # completion rate = batch x token-rate / remaining mass (fluid drain
    # of the residual-token distribution; integrates to the full batch).
    comps = xp.minimum(
        d_batch * tokens_out / xp.maximum(d_tokens, _EPS), d_batch)
    d_tokens = xp.maximum(d_tokens - tokens_out, 0.0)
    drained = d_tokens <= _EPS
    comps = xp.where(drained, d_batch, comps)
    d_batch = xp.where(drained, 0.0, xp.maximum(d_batch - comps, 0.0))
    work_t = work_t + _W_TOKEN_ITER * iters + comps * _W_TOKEN_FINISH
    comps_total = xp.sum(comps)

    # 5) CPU layer (Little's law): nominal work executes at the settled
    # mean core speed; overflow carries as oversubscription backlog.
    work = xp.concatenate([work_p, work_t])
    todo = cpu_backlog + work
    cap = active * dt * sm
    done = xp.minimum(todo, cap)
    cpu_backlog = todo - done
    u = done / (dt * sm)                       # busy cores (fractional)
    ov = cpu_backlog / _MEAN_TASK_S            # oversubscribed tasks
    cpu_wait = xp.mean(xp.minimum(
        cpu_backlog / xp.maximum(active * sm, _EPS), shape.wait_cap_s))

    q2 = (pq_s, pq_n, pq_out, d_batch, d_tokens, d_pend, d_pend_tok,
          cpu_backlog)
    obs = {
        "u": u, "ov": ov, "done": done,
        # the window means, by their _MW_ROWS names
        "wait": wait_p,
        "iter": xp.mean(iter_period),
        "cpuw": cpu_wait,
        "sp": xp.mean(sp), "st": xp.mean(st),
        "comps": comps_total,
    }
    return q2, obs


def _settle_aging(xp, shape: _Shape, dvth, gated, busy_s, advance):
    """Settle one idling period of aging: every non-gated core spends
    its accumulated busy core-seconds at active-allocated stress and the
    remainder of the period at active-unallocated stress; gated cores
    are frozen (ADF = 0). Exact per regime — the NBTI recursion composes
    under a constant ADF."""
    period = shape.steps_per_period * shape.dt_s
    tau_busy = xp.minimum(busy_s, period)
    tau_idle = period - tau_busy
    dvth = advance(dvth, gated, tau_busy,
                   temperature.TEMP_ACTIVE_ALLOCATED_C)
    dvth = advance(dvth, gated, tau_idle,
                   temperature.TEMP_ACTIVE_UNALLOCATED_C)
    return dvth


def _gate_correction(xp, N, active_n, u, ov, g_now, carbon):
    """Vectorized Algorithm 2 reaction (`idling.core_correction`), with
    the optional carbon-aware temporal reshaping. `N` is the core count,
    per machine (M,) on ragged fleets."""
    tasks = xp.minimum(N * 1.0, u + ov)
    e = (active_n - tasks) / N
    f = xp.where(e >= 0.0, xp.tan(0.785 * e), xp.arctan(1.55 * e))
    corr = xp.trunc(N * f)
    if carbon is not None:
        g_mean, dirty_frac, defer_frac, guard, gain = carbon
        dirty = g_now > dirty_frac * g_mean
        amplified = xp.trunc(corr * gain)
        deferred = corr + xp.trunc(-corr * defer_frac)
        corr = xp.where(
            dirty & (corr > 0), amplified,
            xp.where(dirty & (corr < 0) & (ov <= guard), deferred, corr))
    return corr


def _apply_gating(xp, corr, gated, busy_n, dvth, failed=None):
    """Vectorized `idling.apply_correction`: gate `corr` most-aged
    spare active cores (+) or wake `-corr` least-aged gated cores (-)
    per machine, by rank selection along the core axis. `failed`
    (fault layer) excludes permanently-offlined cores from both sides —
    they are never active and must never be woken; `None` leaves the
    selection identical to the pre-fault behavior."""
    active = ~gated if failed is None else ~gated & ~failed
    wakeable = gated if failed is None else gated & ~failed
    eligible = xp.sum(active, axis=1) - busy_n
    k_gate = xp.clip(corr, 0.0, xp.maximum(eligible, 0.0))
    key = xp.where(active, dvth, -np.inf)
    rank_g = xp.argsort(xp.argsort(-key, axis=1), axis=1)
    gate_new = rank_g < k_gate[:, None]
    k_wake = xp.clip(-corr, 0.0, xp.sum(wakeable, axis=1))
    keyw = xp.where(wakeable, dvth, np.inf)
    rank_w = xp.argsort(xp.argsort(keyw, axis=1), axis=1)
    wake = rank_w < k_wake[:, None]
    return (gated | gate_new) & ~wake


def _redistribute_queues(xp, q, onset, up, P):
    """Crash onset: move a down machine's fluid queue mass to the live
    machines of its serving group — the fluid analog of the event
    engine's re-dispatch — and return (q', re-dispatched request mass).
    `onset` is the per-machine crash-onset mask for this macro period,
    `up` the per-machine up-fraction column."""
    (pq_s, pq_n, pq_out, d_batch, d_tokens, d_pend, d_pend_tok,
     cpu_backlog) = q
    live = (up > 0.5) & ~onset

    def move(col, on, upm):
        lost = xp.where(on, col, 0.0)
        tot = xp.sum(lost)
        n_up = xp.sum(upm)
        share = xp.where(upm, tot / xp.maximum(n_up, 1), 0.0)
        # nowhere to go (whole group down): keep the mass in place
        return xp.where(n_up > 0, col - lost + share, col)

    on_p, on_t = onset[:P], onset[P:]
    up_p, up_t = live[:P], live[P:]
    retried = (xp.sum(xp.where(on_p, pq_n, 0.0))
               + xp.sum(xp.where(on_t, d_batch + d_pend, 0.0)))
    q2 = (move(pq_s, on_p, up_p), move(pq_n, on_p, up_p),
          move(pq_out, on_p, up_p), move(d_batch, on_t, up_t),
          move(d_tokens, on_t, up_t), move(d_pend, on_t, up_t),
          move(d_pend_tok, on_t, up_t), move(cpu_backlog, onset, live))
    return q2, retried


class _FleetFaults:
    """Vectorized fault layer for the fleet engine (`repro.faults`).

    The event engine applies fault decisions per machine per tick; the
    fleet surrogate applies the same three built-in models as capacity
    columns and masks:

      machine-crash    — the crash/reboot timeline is *precomputed* from
                         the same per-machine RNG streams
                         (`default_rng([seed, 0xFA, mid])`, Exp(mttf)
                         inter-arrivals): per-macro up-fraction columns
                         scale each machine's CPU capacity, and queue
                         mass is redistributed to live machines at each
                         crash onset (fluid re-dispatch).
      transient-stall  — onsets replayed from the same streams (two
                         draws per machine per period, like the event
                         model) into per-macro capacity multipliers:
                         one core at `slowdown` x speed for `stall_s`.
      guardband        — dynamic (depends on the evolving aging state):
                         each core draws an Exp(1) failure threshold up
                         front; per macro the cumulative hazard
                         `hazard_per_s * period * max(over, 0)`
                         integrates inside the scan and a core fails
                         when it crosses its threshold (inverse-CDF
                         sampling of the first failure under the
                         time-varying hazard — same hazard law as the
                         event model, without per-tick uniforms).
                         Failed cores leave the active set permanently
                         and freeze (like DEEP_IDLE parking).

    What stays approximate: GPU queues of a down machine keep draining
    (capacity loss is modeled in the CPU layer only), and failures land
    at macro boundaries. Engine parity under faults is therefore NOT
    pinned — fault experiments at fleet scale are surrogate estimates,
    the event engine is the reference.
    """

    def __init__(self, cfg: ExperimentConfig, shape: _Shape):
        from repro.faults import get_fault_model
        model = get_fault_model(cfg.fault_model, **cfg.fault_options)
        if model.name not in ("guardband", "machine-crash",
                              "transient-stall"):
            raise ValueError(
                f"fleet engine cannot vectorize fault model "
                f"{model.name!r}; run it under engine='event'")
        M, N = shape.n_machines, shape.num_cores
        # per-machine core counts (ragged fleets; uniform otherwise)
        counts = ([N] * M if shape.core_counts is None
                  else list(shape.core_counts))
        self.period = shape.steps_per_period * shape.dt_s
        self.kind = model.name
        # neutral columns; the matching branch below fills its own
        self.up_frac = np.ones((shape.n_macro, M))
        self.onset = np.zeros((shape.n_macro, M), dtype=bool)
        self.cap_mult = np.ones((shape.n_macro, M))
        self.guard = None
        self.thresh = None
        self.n_crashes = 0
        self.n_stalls = 0
        self.static_lost_core_s = 0.0
        self.windows: list[tuple[float, float]] = []
        dur = shape.duration_s
        rngs = [np.random.default_rng([cfg.seed, 0xFA, mid])
                for mid in range(M)]
        if self.kind == "guardband":
            self.guard = (model.margin, model.hazard_per_s)
            # per-machine failure budgets; the padded lanes of a ragged
            # fleet get an infinite threshold so they can never fail
            self.max_failed_n = np.array(
                [float(int(model.max_failed_frac * n)) for n in counts])
            self.thresh = np.full((M, N), np.inf)
            for mid, (r, n) in enumerate(zip(rngs, counts)):
                self.thresh[mid, :n] = r.exponential(1.0, size=n)
        elif self.kind == "machine-crash":
            for mid, rng in enumerate(rngs):
                t = float(rng.exponential(model.mttf_s))
                while t < dur:
                    self.n_crashes += 1
                    down_until = t + model.reboot_s
                    t1 = min(down_until, dur)
                    self.onset[min(int(t / self.period),
                                   shape.n_macro - 1), mid] = True
                    self._subtract_overlap(self.up_frac, mid, t, t1)
                    self.static_lost_core_s += counts[mid] * (t1 - t)
                    self.windows.append((t, t1))
                    t = down_until + float(rng.exponential(model.mttf_s))
        else:   # transient-stall
            p = -np.expm1(-model.rate_per_s * self.period)
            for mid, rng in enumerate(rngs):
                slow_loss = (1.0 - model.slowdown) / counts[mid]
                for k in range(shape.n_macro):
                    u = float(rng.random())
                    rng.integers(N)      # core id (capacity-aggregated)
                    if u >= p:
                        continue
                    self.n_stalls += 1
                    t0 = (k + 1) * self.period
                    t1 = min(t0 + model.stall_s, dur)
                    self._subtract_overlap(self.cap_mult, mid, t0, t1,
                                           slow_loss)
                    if t1 > t0:
                        self.windows.append((t0, t1))

    def _subtract_overlap(self, col, mid, t0, t1, weight=1.0):
        """Subtract from `col[:, mid]` the share of each macro period
        that [t0, t1) covers, times `weight`."""
        last = col.shape[0] - 1
        for k in range(min(int(t0 / self.period), last),
                       min(int(t1 / self.period), last) + 1):
            lo, hi = max(t0, k * self.period), min(t1, (k + 1) * self.period)
            if hi > lo:
                col[k, mid] -= (hi - lo) / self.period * weight

    def robustness(self, state, completed: int, submitted: int) -> dict:
        """Fleet-side robustness scalars (same keys the event engine's
        `FaultCoordinator.robustness` produces)."""
        from repro.sim.cluster import _merge_intervals
        sh_lost = float(state.get("lost_core_s", 0.0))
        lost = sh_lost + self.static_lost_core_s
        core_failures = (int(state["failed"].sum())
                         if self.guard is not None else 0)
        widths = [hi - lo for lo, hi in _merge_intervals(self.windows)]
        if self.guard is not None and core_failures:
            # failures land at macro boundaries; each degrades the
            # machine for ~one re-sizing period
            widths.append(self.period)
        return {
            "core_failures": core_failures,
            "machine_crashes": self.n_crashes,
            "stalls": self.n_stalls,
            "retries": int(round(float(state.get("retried", 0.0)))),
            "failed_requests": 0,
            "rejected_requests": 0,
            "submitted": submitted,
            "pending_requests": max(submitted - completed, 0),
            "p99_degraded_window_s": (
                float(np.percentile(np.asarray(widths), 99))
                if widths else 0.0),
            "_lost_core_s": lost,
        }


def _derived(xp, f0, dvth, gated, headroom):
    """Per-period derived quantities: settled per-core speeds `f`, the
    per-machine active-core mean speed `sm` used by the fluid layers and
    the active-core count `active_n` (in the speeds' dtype)."""
    f = f0 * (1.0 - dvth / headroom)
    active = ~gated
    active_n = xp.sum(active, axis=1).astype(f.dtype)
    sm = xp.sum(xp.where(active, f, 0.0), axis=1) / xp.maximum(
        active_n, 1.0)
    return f, sm, active_n


@dataclasses.dataclass(frozen=True)
class _Run:
    """What `_macro_step` reads besides state and inputs: the run's
    constants in the backend's dtypes, and the backend's own pieces."""
    xp: Any               # numpy or jax.numpy
    int: Any              # index dtype: int64 on numpy, int32 on jax
    scan: Any             # scan(body, carry, xs) -> (carry, ys), as lax.scan
    add_at: Any           # add_at(a, idx, v): `a` with `v` added at a[idx]
    advance: Any          # advance(dvth, gated, tau, temp_c) -> dvth'
    shape: _Shape
    f0: Any               # (M, N) initial per-core frequencies
    headroom: float
    pad: Any              # (M, N) padded lanes of a ragged fleet, or None
    n_vec: Any            # (M,) core counts of a ragged fleet, or the count
    carbon: Any           # carbon-aware gating parameters, or None
    faults: Any           # the run's _FleetFaults, or None
    thresh: Any           # (M, N) guardband failure thresholds, or None


def _np_scan(body, carry, xs):
    """`jax.lax.scan` for the numpy backend: a Python loop over the
    leading axis of the tuple `xs`, stacking the per-step outputs."""
    ys = []
    for i in range(len(xs[0])):
        carry, y = body(carry, tuple(x[i] for x in xs))
        ys.append(y)
    if ys[0] is None:
        return carry, None
    return carry, tuple(np.stack(y) for y in zip(*ys))


def _np_add_at(a, idx, v):
    np.add.at(a, idx, v)
    return a


def _to_jax(v):
    """A numpy array for the jax backend: bool stays bool, integers
    become int32 and everything else float32."""
    import jax.numpy as jnp
    v = np.asarray(v)
    if v.dtype == bool:
        return jnp.asarray(v)
    return jnp.asarray(v, jnp.int32 if v.dtype.kind in "iu"
                       else jnp.float32)


def _macro_step(run: _Run, st: dict, xs):
    """A scan body that advances the fleet one macro (idling) period:
    the micro steps with their window, residency and busy-time sums,
    then, in the event engine's periodic-tick order, aging settlement,
    guardband failures, the idle histogram and gating. `xs` is one row
    of `FleetEngine._inputs`."""
    xp, sh, fx = run.xp, run.shape, run.faults
    pad, n_vec = run.pad, run.n_vec
    guard_on = fx is not None and fx.guard is not None
    arr_rows, ts, g_now = xs[:3]
    gated_eff = (st["gated"] | st["failed"]) if guard_on else st["gated"]
    if pad is not None:
        gated_eff = gated_eff | pad
    f, sm, active_n = _derived(xp, run.f0, st["dvth"], gated_eff,
                               run.headroom)
    if fx is not None:
        up_row, onset_row, mult_row = xs[3:]
        # capacity columns: stalls scale speed, crashes scale the live
        # core count
        sm = sm * mult_row
        active_n = active_n * up_row
        # a machine with no live cores has zero capacity (via active_n)
        # but must keep a finite nominal speed for the 1/speed
        # bookkeeping terms
        f_all = xp.mean(f, axis=1) if pad is None \
            else xp.sum(f * ~pad, axis=1) / n_vec
        sm = xp.where(active_n > 0, sm, f_all)
    dyn = (sm[:sh.n_prompt], sm[sh.n_prompt:], sm, active_n)
    q = tuple(st[k] for k in _QUEUE_KEYS)
    if fx is not None:
        q, retried = _redistribute_queues(xp, q, onset_row, up_row,
                                          sh.n_prompt)

    def micro(carry, x):
        q, acc, dyn, gated = carry
        arr_row, t = x
        q, obs = _micro_step(xp, sh, dyn, q, arr_row)
        sm, active_n = dyn[2:]
        u, ov, done = obs["u"], obs["ov"], obs["done"]
        # streaming window sums (bounded memory for any horizon)
        w = xp.minimum((t / sh.mwin_s).astype(run.int), sh.n_mwin - 1)
        pw = xp.minimum((t / sh.pwin_s).astype(run.int), sh.n_pwin - 1)
        busy_cs = done / sm
        tasks = u + ov
        acc["mw"] = run.add_at(acc["mw"], (slice(None), w), xp.stack(
            [1.0] + [obs[r] for r in _MW_ROWS[1:]]))
        acc["res"] = run.add_at(acc["res"], (slice(None), slice(None), pw),
                                xp.stack([
                                    busy_cs, active_n * sh.dt_s - busy_cs,
                                    (n_vec - active_n) * sh.dt_s, done],
                                    axis=0))
        acc["task_sum"] = acc["task_sum"] + tasks.sum()
        acc["task_cnt"] = acc["task_cnt"] + tasks.size
        acc["task_max"] = xp.maximum(acc["task_max"], tasks.max())
        acc["completions"] = acc["completions"] + obs["comps"]
        # spread busy time evenly over this period's active set
        acc["busy_s"] = acc["busy_s"] + xp.where(
            gated, 0.0, (busy_cs / xp.maximum(active_n, 1.0))[:, None])
        return (q, acc, dyn, gated), (u, ov)

    acc = {k: st[k] for k in ("mw", "res", "task_sum", "task_cnt",
                              "task_max", "completions", "busy_s")}
    (q, acc, _, _), (us, ovs) = run.scan(
        micro, (q, acc, dyn, gated_eff), (arr_rows, ts))
    u, ov = us[-1], ovs[-1]
    dvth = _settle_aging(xp, sh, st["dvth"], gated_eff, acc["busy_s"],
                         run.advance)
    failed = st.get("failed")
    if guard_on:
        margin, hazard = fx.guard
        over = (dvth / run.headroom - margin) / margin
        haz = hazard * fx.period * xp.maximum(over, 0.0)
        cum = st["cum_haz"] + xp.where(failed, 0.0, haz)
        cand = (cum >= run.thresh) & ~failed
        allowed = xp.maximum(
            fx.max_failed_n
            - xp.sum(failed, axis=1).astype(dvth.dtype), 0.0)
        key = xp.where(cand, cum - run.thresh, -np.inf)
        rank = xp.argsort(xp.argsort(-key, axis=1), axis=1)
        failed = failed | (cand & (rank < allowed[:, None]))
    idle_norm = (active_n - u - ov) / n_vec
    bins = xp.clip(((idle_norm + 1.0) * 0.5
                    * (_IDLE_BINS - 1)).astype(run.int), 0, _IDLE_BINS - 1)
    idle_hist = run.add_at(st["idle_hist"], bins, 1)
    gated = st["gated"]
    if sh.gating:
        corr = _gate_correction(xp, n_vec, active_n, u, ov, g_now,
                                run.carbon)
        # padded lanes behave like permanently failed cores: never
        # gateable, never wakeable
        fail_eff = failed
        if pad is not None:
            fail_eff = pad if fail_eff is None else fail_eff | pad
        gated = _apply_gating(xp, corr, gated,
                              xp.ceil(xp.minimum(u, active_n)), dvth,
                              failed=fail_eff)
    st = dict(st)
    st.update(acc)
    st.update(zip(_QUEUE_KEYS, q))
    st["busy_s"] = xp.zeros_like(acc["busy_s"])
    st["dvth"] = dvth
    st["gated"] = gated
    st["idle_hist"] = idle_hist
    st["u_last"], st["ov_last"] = u, ov
    if fx is not None:
        st["retried"] = st["retried"] + retried
        if guard_on:
            st["failed"] = failed
            st["cum_haz"] = cum
            st["lost_core_s"] = (st["lost_core_s"] + xp.sum(failed)
                                 .astype(dvth.dtype) * fx.period)
    return st, None


# ---------------------------------------------------------------------- #
# Engine
# ---------------------------------------------------------------------- #
class FleetEngine:
    """Time-stepped vectorized fleet simulator (see module docstring).

    ``engine_opts`` (via ``ExperimentConfig.engine_opts``):

    * ``dt_s`` (default 0.25) — fluid micro-step width, seconds.
    * ``backend`` — "numpy" | "jax" | "auto" (default "auto").
    * ``use_kernel`` — route the jax aging settle through the Pallas
      kernel (default: only on TPU, compiled; the jnp oracle elsewhere.
      ``True`` off-TPU runs the kernel in interpret mode).
    * ``checkpoint_dir`` — directory for periodic fleet checkpoints
      (written through ``repro.checkpoint.store``).
    * ``checkpoint_every_s`` (default 600) — simulated seconds between
      checkpoints.
    * ``resume`` (default True) — resume from the latest checkpoint in
      ``checkpoint_dir`` whose config fingerprint matches.
    """

    def __init__(self, cfg: ExperimentConfig, telemetry=None):
        opts = cfg.engine_options
        unknown = set(opts) - {"dt_s", "backend", "use_kernel",
                               "checkpoint_dir", "checkpoint_every_s",
                               "resume"}
        if unknown:
            raise ValueError(f"unknown engine_opts {sorted(unknown)}")
        self.cfg = cfg
        self.telemetry = telemetry
        self.backend = _resolve_backend(str(opts.get("backend", "auto")))
        self.checkpoint_dir = opts.get("checkpoint_dir")
        self.checkpoint_every_s = float(opts.get("checkpoint_every_s",
                                                 600.0))
        self.resume = bool(opts.get("resume", True))
        # The jax backend's aging-settle path, resolved here only and
        # reported in the telemetry "engine" event.
        on_tpu = False
        if self.backend == "jax":
            import jax
            on_tpu = jax.default_backend() == "tpu"
        self.use_kernel = (self.backend == "jax"
                           and bool(opts.get("use_kernel", on_tpu)))
        self.interpret = self.use_kernel and not on_tpu
        # jax: lowering and compiling the macro scan, the span
        # `fleetsim.compile` and the gauge `phase/jax_compile_wall_s`
        self._compile = Phase("fleetsim.compile",
                              telemetry if self.backend == "jax" else None,
                              gauge="jax_compile")

        dt = float(opts.get("dt_s", 0.25))
        if dt <= 0:
            raise ValueError(f"dt_s must be > 0, got {dt}")
        dt = min(dt, cfg.idling_period_s)
        spp = max(1, round(cfg.idling_period_s / dt))
        dt = cfg.idling_period_s / spp          # align to the period
        n_macro = max(1, int(round(cfg.duration_s / cfg.idling_period_s)))
        mwin = max(dt, cfg.duration_s / 512.0)
        pwin = cfg.resolved_power_window_s
        # Heterogeneous fleet (`repro.hardware`): None for the uniform
        # default, in which case every branch below runs the legacy
        # bit-exact path with zero ragged bookkeeping. Mixed fleets pad
        # the core axis to the widest SKU and mask the extra lanes.
        self.inventory = resolve_fleet(cfg.fleet, cfg.fleet_options,
                                       cfg.n_machines)
        inv = self.inventory
        if inv is None:
            self.params = aging.DEFAULT_PARAMS
            num_cores, core_counts = cfg.num_cores, None
        else:
            # one shared NBTI operating point (raises for mixed Vdd/Vth
            # fleets — those need the per-machine event engine)
            self.params = inv.shared_dynamics_params()
            num_cores, core_counts = inv.max_cores, tuple(inv.num_cores)
        self.shape = _Shape(
            n_prompt=cfg.n_prompt, n_token=cfg.n_token,
            num_cores=num_cores, dt_s=dt, steps_per_period=spp,
            n_macro=n_macro,
            mwin_s=mwin, n_mwin=int(np.ceil(cfg.duration_s / mwin)) + 1,
            pwin_s=pwin, n_pwin=int(np.ceil(cfg.duration_s / pwin)) + 1,
            duration_s=cfg.duration_s,
            mean_out_tokens=0.0,        # set from the trace in run()
            gating=cfg.policy == "proposed",
            core_counts=core_counts,
        )
        # Same per-machine initial-frequency draw as the event engine's
        # CoreManager (seeded rng per machine), so both engines simulate
        # literally the same silicon — on mixed fleets each machine
        # draws its own SKU's variation parameters and core count.
        if inv is None:
            vp = variation.VariationParams(f_nominal=self.params.f_nominal)
            self.f0 = np.stack([
                variation.sample_initial_frequencies(
                    vp, cfg.num_cores,
                    np.random.default_rng(cfg.seed * 1000 + i))
                for i in range(self.shape.n_machines)])
            self._pad = None
            self._n_vec = None
        else:
            self.f0 = np.ones((self.shape.n_machines, num_cores))
            for i, n in enumerate(core_counts):
                self.f0[i, :n] = variation.sample_initial_frequencies(
                    inv.variation_params[i], n,
                    np.random.default_rng(cfg.seed * 1000 + i))
            self._pad = (np.arange(num_cores)[None, :]
                         >= np.asarray(core_counts)[:, None])
            self._n_vec = np.asarray(core_counts, dtype=np.float64)
        self._carbon_gate = self._resolve_carbon_gate(cfg)
        self.state = _initial_state(self.shape)
        # Fault layer (None with the default "none" model — the state
        # dict, scan signature and physics stay exactly the pre-fault
        # ones, so faultless runs are bit-identical on both backends).
        self._faults = (_FleetFaults(cfg, self.shape)
                        if cfg.fault_model != "none" else None)
        if self._faults is not None:
            self.shape.wait_cap_s = cfg.duration_s
            self.state["lost_core_s"] = np.zeros(())
            self.state["retried"] = np.zeros(())
            if self._faults.guard is not None:
                self.state["failed"] = np.zeros(
                    (self.shape.n_machines, self.shape.num_cores),
                    dtype=bool)
                self.state["cum_haz"] = np.zeros(
                    (self.shape.n_machines, self.shape.num_cores))
        self.resumed_from: int | None = None

    @staticmethod
    def _resolve_carbon_gate(cfg: ExperimentConfig):
        """(intensity_fn, params) for carbon-aware proposed configs."""
        popts = cfg.policy_options
        if cfg.policy != "proposed" or not popts.get("carbon_aware"):
            return None
        from repro.carbon.intensity import get_intensity
        intensity = get_intensity(popts.get("intensity", "diurnal"),
                                  **dict(popts.get("intensity_opts") or {}))
        return (intensity,
                (intensity.mean_g_per_kwh(),
                 float(popts.get("dirty_frac", 1.05)),
                 float(popts.get("defer_frac", 0.5)),
                 float(popts.get("guard_tasks", 2)),
                 float(popts.get("gate_gain", 2.0))))

    # ------------------------------------------------------------------ #
    # trace binning
    # ------------------------------------------------------------------ #
    def _bin_trace(self, requests: RequestColumns) -> np.ndarray:
        """(T_micro, 3) per-step [arrival count, input-token sum,
        output-token sum] from the scenario's request trace."""
        sh = self.shape
        n_steps = sh.n_macro * sh.steps_per_period
        out = np.zeros((n_steps, 3))
        if not len(requests):
            return out
        t_arr, n_in, n_out = _float_columns(requests)
        idx = np.clip((t_arr / sh.dt_s).astype(np.int64), 0, n_steps - 1)
        out[:, 0] = np.bincount(idx, minlength=n_steps)
        out[:, 1] = np.bincount(idx, weights=n_in, minlength=n_steps)
        out[:, 2] = np.bincount(idx, weights=n_out, minlength=n_steps)
        return out

    # ------------------------------------------------------------------ #
    # run
    # ------------------------------------------------------------------ #
    def run(self, requests) -> None:
        """Run the trace: `RequestColumns`, or a `Request` sequence,
        which is turned into columns once (the span `fleetsim.bin`
        counts those requests as `converted`)."""
        sh = self.shape
        with span("fleetsim.bin") as sp:
            converted = 0
            if not isinstance(requests, RequestColumns):
                requests = RequestColumns.from_requests(requests)
                converted = len(requests)
            sp.set_metadata(converted=converted)
            sh.mean_out_tokens = (float(np.mean(requests.output_tokens))
                                  if len(requests) else 1.0)
            arr = self._bin_trace(requests)
        self._requests = requests
        start = 0
        if self.checkpoint_dir and self.resume:
            start = self._try_resume()
        if self.backend == "jax":
            self._run_jax(arr, start)
        else:
            self._run_numpy(arr, start)

    # -- checkpoint/resume --------------------------------------------- #
    def _checkpoint(self, macro: int) -> None:
        from repro.checkpoint import store
        store.save(self.checkpoint_dir, macro, self.state,
                   extra={"config": self.cfg.fingerprint(),
                          "engine": "fleet", "backend": self.backend,
                          "macro": macro})

    def _try_resume(self) -> int:
        from repro.checkpoint import store
        if store.latest_step(self.checkpoint_dir) is None:
            return 0
        template = {k: np.asarray(v) for k, v in self.state.items()}
        # step=None lets the store digest-verify the newest checkpoint
        # and fall back (with a warning) to the newest earlier step that
        # verifies, so one torn write doesn't strand the whole run.
        restored = store.restore(self.checkpoint_dir, template)
        # copy: restored arrays can be read-only views of the npz buffer
        state = {k: np.array(v) for k, v in restored.items()}
        step = int(state["macro"])      # save() labels steps by macro
        meta = store.meta(self.checkpoint_dir, step)
        if meta.get("config") != self.cfg.fingerprint():
            raise ValueError(
                f"checkpoint at {self.checkpoint_dir!r} step {step} was "
                f"written by config {meta.get('config')!r}, not "
                f"{self.cfg.fingerprint()!r}: refusing to resume a "
                f"different experiment")
        self.state = state
        self.resumed_from = step
        return int(self.state["macro"])

    # -- drivers -------------------------------------------------------- #
    def _inputs(self, arr: np.ndarray) -> tuple:
        """Every macro step's inputs (float64) along the leading axis:
        the micro steps' binned arrivals and start times, the carbon
        intensity at the period's end, and any fault columns."""
        sh = self.shape
        spp = sh.steps_per_period
        ts = np.arange(sh.n_macro * spp) * sh.dt_s
        if self._carbon_gate:
            t_end = (np.arange(sh.n_macro) + 1) * spp * sh.dt_s
            g = np.array([self._carbon_gate[0].g_per_kwh(t) for t in t_end])
        else:
            g = np.zeros(sh.n_macro)
        xs = (arr.reshape(sh.n_macro, spp, 3), ts.reshape(sh.n_macro, spp),
              g)
        fx = self._faults
        if fx is not None:
            xs += (fx.up_frac, fx.onset, fx.cap_mult)
        return xs

    def _step_run(self, xp, cast, **ops) -> _Run:
        """This run's `_Run` for `xp`, its constant arrays through
        `cast`."""
        fx = self._faults
        guard_on = fx is not None and fx.guard is not None
        return _Run(
            xp=xp, shape=self.shape, f0=cast(self.f0),
            headroom=self.params.headroom,
            pad=None if self._pad is None else cast(self._pad),
            n_vec=(self.shape.num_cores if self._pad is None
                   else cast(self._n_vec)),
            carbon=self._carbon_gate[1] if self._carbon_gate else None,
            faults=fx, thresh=cast(fx.thresh) if guard_on else None, **ops)

    def _drive(self, advance, carry: dict, xs: tuple,
               start_macro: int) -> None:
        """Advance macro steps `start_macro..n_macro` in chunks that end
        at each checkpoint; `advance(carry, xs_chunk)` returns the carry
        after the chunk, which `_unpack` writes back to `self.state`."""
        sh = self.shape
        per = max(1, int(round(self.checkpoint_every_s
                               / self.cfg.idling_period_s)))
        k = start_macro
        while k < sh.n_macro:
            k2 = min((k // per + 1) * per, sh.n_macro) \
                if self.checkpoint_dir else sh.n_macro
            carry = advance(carry, tuple(x[k:k2] for x in xs))
            k = k2
            with span("fleetsim.unpack"):
                self._unpack(carry, k)
            if self.checkpoint_dir and k < sh.n_macro:
                self._checkpoint(k)

    def _unpack(self, carry: dict, macro: int) -> None:
        """Write a driver's carry back to `self.state`, each array in
        its own numpy dtype."""
        s = self.state
        for key, v in carry.items():
            s[key] = np.asarray(v, dtype=s[key].dtype)
        s["macro"] = np.asarray(macro, dtype=np.int64)

    def _advance_numpy(self, dvth, gated, tau, temp_c):
        a = aging.adf(self.params, temp_c, 1.0)
        tau = np.where(gated, 0.0, np.broadcast_to(tau, dvth.shape))
        return aging.advance_dvth(self.params, dvth, a, tau)

    def _run_numpy(self, arr: np.ndarray, start_macro: int) -> None:
        run = self._step_run(np, np.asarray, int=np.int64, scan=_np_scan,
                             add_at=_np_add_at, advance=self._advance_numpy)
        step = functools.partial(_macro_step, run)
        state = {k: v for k, v in self.state.items() if k != "macro"}
        self._drive(lambda st, xs: _np_scan(step, st, xs)[0], state,
                    self._inputs(arr), start_macro)

    def _build_jax(self, arr: np.ndarray):
        """The jitted macro scan and its inputs: ``(scan, state, xs)``.
        ``scan(state, xs[k:k2])`` advances macro steps ``k..k2``; ``xs``
        holds every macro step's inputs along its leading axis."""
        import jax
        import jax.numpy as jnp
        from repro.kernels.aging_update.ops import advance_fleet

        params = self.params
        use_kernel, interpret = self.use_kernel, self.interpret

        def advance(dvth, gated, tau, temp_c):
            flat = dvth.reshape(-1)
            stress = jnp.where(gated, 0.0, 1.0).reshape(-1)
            tau_f = jnp.broadcast_to(tau, dvth.shape).reshape(-1)
            temp = jnp.full_like(flat, temp_c)
            out = advance_fleet(flat, temp, stress, tau_f, params,
                                use_kernel=use_kernel, interpret=interpret)
            return out.reshape(dvth.shape)

        run = self._step_run(jnp, _to_jax, int=jnp.int32, scan=jax.lax.scan,
                             add_at=lambda a, idx, v: a.at[idx].add(v),
                             advance=advance)
        step = functools.partial(_macro_step, run)

        @jax.jit
        def fleet_scan(st0, xs):
            return jax.lax.scan(step, st0, xs)
        jst = {k: _to_jax(v) for k, v in self.state.items() if k != "macro"}
        return fleet_scan, jst, tuple(_to_jax(x) for x in self._inputs(arr))

    def _run_jax(self, arr: np.ndarray, start_macro: int) -> None:
        import jax
        with span("fleetsim.build"):
            scan, jst, xs = self._build_jax(arr)
        compiled = {}       # chunk length -> executable, compiled ahead

        def advance(jst, chunk):
            n = chunk[0].shape[0]
            step = compiled.get(n)
            if step is None:
                with self._compile:
                    step = compiled[n] = scan.lower(jst, chunk).compile()
            with span("fleetsim.scan"):
                jst, _ = step(jst, chunk)
                jax.block_until_ready(jst)
            return jst
        self._drive(advance, jst, xs, start_macro)

    # ------------------------------------------------------------------ #
    # collection
    # ------------------------------------------------------------------ #
    def _window_means(self):
        """Per-window means of the micro-step observables, keyed by
        their _MW_ROWS names."""
        mw = dict(zip(_MW_ROWS, self.state["mw"]))
        cnt = np.maximum(mw["cnt"], 1.0)
        means = {k: mw[k] / cnt for k in ("wait", "iter", "cpuw", "sp", "st")}
        # empty windows fall back to the run-wide mean
        have = mw["cnt"] > 0
        for k, v in means.items():
            overall = float(v[have].mean()) if have.any() else \
                (1.0 if k in ("sp", "st") else 0.0)
            means[k] = np.where(have, v, overall)
        return means

    def _latency_postpass(self):
        """Per-request latency estimates from the windowed aggregates —
        a transient vectorized pass over the trace (no per-request state
        is held by the engine)."""
        sh = self.shape
        if not len(self._requests):
            return float("nan"), float("nan"), 0
        mw = self._window_means()
        t_arr, n_in, n_out = _float_columns(self._requests)
        w = np.clip((t_arr / sh.mwin_s).astype(np.int64), 0,
                    sh.n_mwin - 1)
        sp = mw["sp"][w]
        wait = mw["wait"][w] + mw["cpuw"][w]
        prefill = (TASK_DURATIONS_S["submit_task"] / sp
                   + PREFILL_BASE_S + PREFILL_PER_TOKEN_S * n_in)
        w2 = np.clip(((t_arr + wait + prefill) / sh.mwin_s)
                     .astype(np.int64), 0, sh.n_mwin - 1)
        itp = mw["iter"][w2]
        lat = (_LAT_CPU_PROMPT / sp + wait + prefill
               + n_in * _KV_S_PER_TOKEN
               + _W_TOKEN_ARRIVAL / mw["st"][w2]
               + 0.5 * itp + n_out * itp)
        done = t_arr + lat <= sh.duration_s
        if not done.any():
            return float("nan"), float("nan"), 0
        lat_done = lat[done]
        return (float(lat_done.mean()),
                float(np.percentile(lat_done, 99)),
                int(done.sum()))

    def _idle_percentiles(self):
        hist = self.state["idle_hist"].astype(np.float64)
        total = hist.sum()
        if total <= 0:
            zeros = {p: 0.0 for p in metrics_mod.PERCENTILES}
            return zeros, 0.0
        edges = np.linspace(-1.0, 1.0, _IDLE_BINS + 1)
        cdf = np.cumsum(hist) / total
        pcts = {}
        for p in metrics_mod.PERCENTILES:
            i = int(np.searchsorted(cdf, p / 100.0))
            i = min(i, _IDLE_BINS - 1)
            c0 = cdf[i - 1] if i > 0 else 0.0
            span = cdf[i] - c0
            frac = ((p / 100.0 - c0) / span) if span > 0 else 0.5
            pcts[p] = float(edges[i] + frac * (edges[i + 1] - edges[i]))
        below = float(hist[:int((1.0 - 0.1) * 0.5
                                * (_IDLE_BINS - 1))].sum() / total)
        return pcts, below

    def residencies(self) -> tuple[StateResidency, ...]:
        sh = self.shape
        res = dict(zip(_RES_ROWS, self.state["res"]))
        out = []
        for m in range(sh.n_machines):
            out.append(StateResidency(
                num_cores=(sh.num_cores if sh.core_counts is None
                           else sh.core_counts[m]),
                duration_s=sh.duration_s,
                busy_core_s=float(res["busy"][m].sum()),
                idle_core_s=float(res["idle"][m].sum()),
                gated_core_s=float(res["gated"][m].sum()),
                freq_busy_core_s=float(res["fbusy"][m].sum()),
                window_s=sh.pwin_s,
                window_busy_s=tuple(res["busy"][m]),
                window_idle_s=tuple(res["idle"][m]),
                window_gated_s=tuple(res["gated"][m]),
            ))
        return tuple(out)

    def collect(self, carbon_model=None, power_model=None,
                telemetry=None) -> ExperimentResult:
        with span("fleetsim.collect"):
            return self._collect(carbon_model, power_model, telemetry)

    def _collect(self, carbon_model, power_model,
                 telemetry) -> ExperimentResult:
        sh, st = self.shape, self.state
        f = self.f0 * (1.0 - st["dvth"] / self.params.headroom)
        if self._pad is None:
            cvs = f.std(axis=1) / f.mean(axis=1)
            degs = (self.f0 - f).mean(axis=1)
        else:
            # masked per-machine stats: padded lanes carry no silicon
            w = ~self._pad
            n = self._n_vec
            fm = (f * w).sum(axis=1) / n
            var = (((f - fm[:, None]) ** 2) * w).sum(axis=1) / n
            cvs = np.sqrt(var) / fm
            degs = ((self.f0 - f) * w).sum(axis=1) / n
        idle_pcts, below = self._idle_percentiles()
        mean_lat, p99_lat, completed = self._latency_postpass()
        task_cnt = max(float(st["task_cnt"]), 1.0)
        robustness = None
        if self._faults is not None:
            robustness = self._faults.robustness(
                st, completed, len(self._requests))
            lost = robustness.pop("_lost_core_s")
            robustness["availability"] = 1.0 - min(
                lost / (sh.total_cores * sh.duration_s), 1.0)
        result = metrics_mod.price_and_build(
            self.cfg,
            cvs=cvs, degs=degs,
            idle_norm_percentiles=idle_pcts,
            oversub_frac_below=below,
            task_count_mean=float(st["task_sum"]) / task_cnt,
            task_count_max=int(round(float(st["task_max"]))),
            mean_latency_s=mean_lat, p99_latency_s=p99_lat,
            completed=completed,
            aging_params=self.params,
            elapsed=sh.duration_s,
            residencies=self.residencies(),
            engine="fleet",
            robustness=robustness,
            carbon_model=carbon_model, power_model=power_model,
            telemetry=telemetry,
            fleet_inventory=self.inventory,
        )
        if telemetry is not None:
            self._emit_telemetry(telemetry)
        return result

    def _emit_telemetry(self, hub) -> None:
        """Windowed fleet aggregates into the hub's streaming series —
        ring-buffered, so any horizon stays bounded."""
        sh, st = self.shape, self.state
        means = self._window_means()
        mw = dict(zip(_MW_ROWS, st["mw"]))
        have = np.flatnonzero(mw["cnt"] > 0)
        tl = hub.timeline("fleet/windows", maxlen=max(len(have), 1))
        for i in have:
            t = float(i * sh.mwin_s)
            tl.record(t, (float(means["wait"][i]),
                          float(means["iter"][i]),
                          float(means["cpuw"][i]),
                          float(mw["comps"][i])))
        hub.set_gauge("fleet/completions", float(st["completions"]))
        hub.set_gauge("fleet/gated_cores_final",
                      float(self.state["gated"].sum()))


# ---------------------------------------------------------------------- #
# runner entry point
# ---------------------------------------------------------------------- #
def run_fleet_experiment(cfg: ExperimentConfig, *, telemetry=None,
                         carbon_model=None, power_model=None,
                         scenario=None,
                         requests=None) -> ExperimentResult:
    """Generate the trace, run the fleet engine, collect the result.
    Mirrors `run_experiment`'s event path; `requests` short-circuits
    trace generation when the caller already has it. The whole call is
    the span `fleetsim.experiment`, with the engine's phases inside."""
    with span("fleetsim.experiment", seed=cfg.seed):
        if scenario is None:
            from repro.workloads import get_scenario
            scenario = get_scenario(cfg.scenario, **cfg.scenario_options)
        if requests is None:
            # columns where the scenario draws them; a scenario with only
            # `generate` gives a list, which `run` converts once
            draw = getattr(scenario, "generate_columns", scenario.generate)
            requests = draw(rate_rps=cfg.rate_rps,
                            duration_s=cfg.duration_s, seed=cfg.seed)
        with span("fleetsim.build"):
            engine = FleetEngine(cfg, telemetry=telemetry)
        if telemetry is not None:
            telemetry.event("engine", 0.0, engine="fleet",
                            backend=engine.backend,
                            use_kernel=engine.use_kernel,
                            interpret=engine.interpret)
        engine.run(requests)
        return engine.collect(carbon_model=carbon_model,
                              power_model=power_model, telemetry=telemetry)


__all__: list[str] = ["FleetEngine", "run_fleet_experiment"]
