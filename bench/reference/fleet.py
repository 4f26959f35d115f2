"""Plain float64 reference of the fleet simulation, for a uniform fleet
with no faults and no carbon-aware gating.

It restates, in straightforward numpy, the model that
`repro.sim.fleetsim` documents: a fluid micro step of `dt_s` seconds
(arrivals split evenly over prompt machines, prefill drained at one GPU
second per second, completed prefills split evenly over token machines,
continuous-batch decode whose iteration is stretched by the aged CPU,
per-request CPU work executed at the settled core speed with the
overflow carried as backlog), and once per idling period the exact NBTI
recursion per core, then the proposed policy's gating (Algorithm 2 of
arXiv:2501.15829). After the horizon: per-request latency from windowed
means, mean frequency degradation per machine, and yearly embodied
kgCO2e under the linear lifetime-extension model.

Every constant is copied here, and nothing is imported from the
program, so that a change to the program cannot move the reference.

`store` rounds every stored quantity (the state carried from step to
step and the window sums): the identity for the float64 reference, a
round trip through bfloat16 for its control.
"""
from __future__ import annotations

import math

import numpy as np

# Per-task CPU work, seconds at nominal speed (the Splitwise/ORCA host
# tasks the paper's Table 2 lists).
TASK_S = {
    "submit": 0.020, "submit_chain": 0.010, "submit_flow": 0.0075,
    "submit_task": 0.0075, "finish_flow": 0.005, "finish_request": 0.010,
    "finish_task": 0.005, "alloc_memory": 0.0125, "free_memory": 0.0075,
    "start_iteration": 0.015, "flow_completion": 0.005,
}
PREFILL_BASE_S = 0.030
PREFILL_PER_TOKEN_S = 1.2e-4
DECODE_ITER_BASE_S = 0.025
DECODE_ITER_PER_REQ_S = 4.0e-4
MAX_DECODE_BATCH = 64
KV_S_PER_TOKEN = 320e3 / 25e9

W_PROMPT_ARRIVAL = (TASK_S["submit"] + TASK_S["submit_chain"]
                    + TASK_S["alloc_memory"] + TASK_S["submit_task"])
W_PROMPT_FINISH = TASK_S["finish_task"] + TASK_S["submit_flow"]
W_TOKEN_ARRIVAL = TASK_S["flow_completion"] + TASK_S["alloc_memory"]
W_TOKEN_ITER = TASK_S["start_iteration"]
W_TOKEN_FINISH = TASK_S["free_memory"] + TASK_S["finish_request"]
LAT_CPU_PROMPT = (TASK_S["submit"] + TASK_S["submit_chain"]
                  + TASK_S["alloc_memory"])
MEAN_TASK_S = float(np.mean(list(TASK_S.values())))
EPS = 1e-12

# NBTI (paper section 3.2, Eq. 1-2), 22 nm class.
NBTI_N = 1.0 / 6.0
KB = 8.617333e-5
E0 = 0.1897
C_FIELD = 0.075
VDD, VTH = 1.0, 0.45
HEADROOM = VDD - VTH
F_NOMINAL = 1.0
TEMP_BUSY_C, TEMP_IDLE_C = 54.0, 51.08
TEN_YEARS_S = 10.0 * 365.25 * 24 * 3600.0


def _adf_unscaled(temp_c: float) -> float:
    t_k = temp_c + 273.15
    return math.exp(-E0 / (KB * t_k)) * math.exp(C_FIELD * VDD / (KB * t_k))


# K: a core at 54 C under full stress loses 30% of its frequency in ten
# years.
K_FIT = 0.30 * HEADROOM / (_adf_unscaled(54.0) * TEN_YEARS_S ** NBTI_N)


def adf(temp_c: float) -> float:
    return K_FIT * _adf_unscaled(temp_c)


# Process variation: a 10 x 10 grid of spatially correlated delays.
GRID, ALPHA, SIGMA_FRAC = 10, 0.5, 0.05

# Embodied carbon of one server CPU over its 3-year refresh cycle.
CPU_EMBODIED_KG, BASE_LIFE_YEARS = 278.3, 3.0
MAX_EXTENSION, MIN_EXTENSION = 100.0, 1e-6


def initial_frequencies(num_cores: int, seed: int) -> np.ndarray:
    coords = np.stack(np.meshgrid(np.arange(GRID), np.arange(GRID),
                                  indexing="ij"), -1).reshape(-1, 2)
    d = np.linalg.norm(coords[:, None, :].astype(np.float64)
                       - coords[None, :, :], axis=-1)
    chol = np.linalg.cholesky(np.exp(-ALPHA * d) + 1e-10 * np.eye(GRID ** 2))
    rng = np.random.default_rng(seed)
    p = 1.0 + SIGMA_FRAC * (chol @ rng.standard_normal(GRID ** 2))
    p = np.clip(p, 0.2, None)
    cells = np.array_split(np.arange(GRID ** 2), num_cores)
    return np.array([np.min(1.0 / p[c]) for c in cells])


def _identity(x):
    return x


def bf16_store(x):
    """Round to bfloat16 and back (the control's storage)."""
    import ml_dtypes
    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)


def simulate(config: dict, rate_rps: float, trace, seed: int,
             store=_identity) -> dict:
    """Run the fleet on `trace` = (arrival_s, input, output) arrays and
    return the compared scalars."""
    P, K = config["n_prompt"], config["n_token"]
    M, N = P + K, config["num_cores"]
    dur, period = float(config["duration_s"]), float(config["idling_period_s"])
    dt = min(float(config["dt_s"]), period)
    spp = max(1, round(period / dt))
    dt = period / spp
    n_macro = max(1, int(round(dur / period)))
    mwin = max(dt, dur / 512.0)
    n_mwin = int(np.ceil(dur / mwin)) + 1
    gating = config["policy"] == "proposed"

    f0 = np.stack([initial_frequencies(N, seed * 1000 + i) for i in range(M)])
    t_arr, n_in, n_out = trace
    n_steps = n_macro * spp
    idx = np.clip((t_arr / dt).astype(np.int64), 0, n_steps - 1)
    arr = np.stack([np.bincount(idx, minlength=n_steps).astype(np.float64),
                    np.bincount(idx, weights=n_in, minlength=n_steps),
                    np.bincount(idx, weights=n_out, minlength=n_steps)], 1)

    dvth = np.zeros((M, N))
    gated = np.zeros((M, N), bool)
    pq_s, pq_n, pq_out = np.zeros(P), np.zeros(P), np.zeros(P)
    d_batch, d_tokens, d_pend, d_pend_tok = (np.zeros(K) for _ in range(4))
    backlog = np.zeros(M)
    busy_s = np.zeros((M, N))
    mw = {k: np.zeros(n_mwin) for k in ("cnt", "wait", "iter", "cpuw",
                                         "sp", "st")}
    a_busy, a_idle = adf(TEMP_BUSY_C), adf(TEMP_IDLE_C)

    for k in range(n_macro):
        f = f0 * (1.0 - dvth / HEADROOM)
        active = ~gated
        active_n = active.sum(axis=1).astype(np.float64)
        sm = np.where(active, f, 0.0).sum(axis=1) / np.maximum(active_n, 1.0)
        sp, st = sm[:P], sm[P:]
        for j in range(spp):
            step = k * spp + j
            a, in_sum, out_sum = arr[step]
            wait_p = pq_s.mean()
            # arrivals, split evenly over the prompt machines
            pq_n = pq_n + a / P
            pq_s = pq_s + (a * PREFILL_BASE_S + PREFILL_PER_TOKEN_S * in_sum
                           + a * TASK_S["submit_task"] / sp) / P
            pq_out = pq_out + out_sum / P
            work_p = (a / P) * W_PROMPT_ARRIVAL
            # prefill drains one GPU second per second
            ds = np.minimum(pq_s, dt)
            frac = ds / np.maximum(pq_s, EPS)
            done_n, done_out = pq_n * frac, pq_out * frac
            pq_s, pq_n, pq_out = pq_s - ds, pq_n - done_n, pq_out - done_out
            work_p = work_p + done_n * W_PROMPT_FINISH
            c_total, o_total = done_n.sum(), done_out.sum()
            # completed prefills flow evenly to the token machines
            d_pend = d_pend + c_total / K
            d_pend_tok = d_pend_tok + o_total / K
            work_t = (c_total / K) * W_TOKEN_ARRIVAL
            adm = np.minimum(d_pend, np.maximum(MAX_DECODE_BATCH - d_batch,
                                                0.0))
            per = d_pend_tok / np.maximum(d_pend, EPS)
            d_batch = d_batch + adm
            d_tokens = d_tokens + adm * per
            d_pend = d_pend - adm
            d_pend_tok = d_pend_tok - adm * per
            # decode: the CPU's start_iteration is serial with the GPU pass
            iter_period = (W_TOKEN_ITER / st + DECODE_ITER_BASE_S
                           + DECODE_ITER_PER_REQ_S
                           * np.minimum(d_batch, MAX_DECODE_BATCH))
            iters = np.where(d_batch > EPS, dt / iter_period, 0.0)
            tokens_out = np.minimum(iters * d_batch, d_tokens)
            comps = np.minimum(d_batch * tokens_out
                               / np.maximum(d_tokens, EPS), d_batch)
            d_tokens = np.maximum(d_tokens - tokens_out, 0.0)
            drained = d_tokens <= EPS
            comps = np.where(drained, d_batch, comps)
            d_batch = np.where(drained, 0.0, np.maximum(d_batch - comps, 0.0))
            work_t = work_t + W_TOKEN_ITER * iters + comps * W_TOKEN_FINISH
            # CPU: work runs at the settled mean speed of the active cores
            todo = backlog + np.concatenate([work_p, work_t])
            done = np.minimum(todo, active_n * dt * sm)
            backlog = todo - done
            u = done / (dt * sm)
            ov = backlog / MEAN_TASK_S
            cpu_wait = np.mean(backlog / np.maximum(active_n * sm, EPS))

            pq_s, pq_n, pq_out = store(pq_s), store(pq_n), store(pq_out)
            d_batch, d_tokens = store(d_batch), store(d_tokens)
            d_pend, d_pend_tok = store(d_pend), store(d_pend_tok)
            backlog = store(backlog)
            w = min(int(step * dt / mwin), n_mwin - 1)
            for key, val in (("cnt", 1.0), ("wait", wait_p),
                             ("iter", iter_period.mean()),
                             ("cpuw", cpu_wait), ("sp", sp.mean()),
                             ("st", st.mean())):
                mw[key][w] = store(mw[key][w] + val)
            busy_cs = done / sm
            busy_s = store(busy_s + np.where(
                gated, 0.0, (busy_cs / np.maximum(active_n, 1.0))[:, None]))

        # idling-period boundary: settle aging (busy share at 54 C, the
        # rest at 51.08 C; gated cores frozen), then gate
        tau_busy = np.minimum(busy_s, period)
        for a_k, tau in ((a_busy, tau_busy), (a_idle, period - tau_busy)):
            tau = np.where(gated, 0.0, tau)
            new = a_k * ((dvth / a_k) ** (1.0 / NBTI_N) + tau) ** NBTI_N
            dvth = np.where(tau > 0.0, new, dvth)
        dvth = store(dvth)
        busy_s = np.zeros((M, N))
        if gating:
            tasks = np.minimum(N * 1.0, u + ov)
            e = (active_n - tasks) / N
            corr = np.trunc(N * np.where(e >= 0.0, np.tan(0.785 * e),
                                         np.arctan(1.55 * e)))
            busy_n = np.ceil(np.minimum(u, active_n))
            act, wakeable = ~gated, gated
            k_gate = np.clip(corr, 0.0,
                             np.maximum(act.sum(axis=1) - busy_n, 0.0))
            key = np.where(act, dvth, -np.inf)
            gate_new = (np.argsort(np.argsort(-key, axis=1), axis=1)
                        < k_gate[:, None])
            k_wake = np.clip(-corr, 0.0, wakeable.sum(axis=1))
            keyw = np.where(wakeable, dvth, np.inf)
            wake = (np.argsort(np.argsort(keyw, axis=1), axis=1)
                    < k_wake[:, None])
            gated = (gated | gate_new) & ~wake

    # latency of each request from the windowed means
    have = mw["cnt"] > 0
    means = {}
    for key in ("wait", "iter", "cpuw", "sp", "st"):
        v = mw[key] / np.maximum(mw["cnt"], 1.0)
        means[key] = np.where(have, v, v[have].mean())
    w = np.clip((t_arr / mwin).astype(np.int64), 0, n_mwin - 1)
    sp_r = means["sp"][w]
    wait = means["wait"][w] + means["cpuw"][w]
    prefill = (TASK_S["submit_task"] / sp_r + PREFILL_BASE_S
               + PREFILL_PER_TOKEN_S * n_in)
    w2 = np.clip(((t_arr + wait + prefill) / mwin).astype(np.int64), 0,
                 n_mwin - 1)
    itp = means["iter"][w2]
    lat = (LAT_CPU_PROMPT / sp_r + wait + prefill + n_in * KV_S_PER_TOKEN
           + W_TOKEN_ARRIVAL / means["st"][w2] + 0.5 * itp + n_out * itp)
    done = t_arr + lat <= dur

    # degradation and embodied carbon under linear lifetime extension
    degs = (f0 - f0 * (1.0 - dvth / HEADROOM)).mean(axis=1)
    deg_ref = F_NOMINAL * adf(TEMP_BUSY_C) * max(dur, 1e-9) ** NBTI_N / HEADROOM
    yearly = 0.0
    for d in degs:
        ext = (MAX_EXTENSION if d <= 0.0
               else max(deg_ref / float(d), MIN_EXTENSION))
        yearly += CPU_EMBODIED_KG / (BASE_LIFE_YEARS * ext)
    return {"requests": int(len(t_arr)),
            "completed": int(done.sum()),
            "mean_latency_s": float(lat[done].mean()),
            "mean_degradation_p50": float(np.percentile(degs, 50)),
            "mean_degradation_p99": float(np.percentile(degs, 99)),
            "fleet_yearly_kgco2eq": float(yearly)}
