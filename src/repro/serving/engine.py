"""Continuous-batching inference engine with aging-aware host-CPU core
management — the paper's technique as a first-class serving feature.

The engine owns a fixed pool of batch slots backed by one device-resident
KV cache (per-slot positions), performs ORCA-style iteration-level
scheduling, and routes every host-side operation through a `CoreManager`
(Table-2 task taxonomy): request submission -> `submit`, slot allocation
-> `alloc_memory`, each batched decode iteration -> `start_iteration`,
completion -> `finish_request`/`free_memory`. The manager's Selective
Core Idling runs on a wall-clock period, so an idle engine deep-idles its
host cores (age-halting) and a bursty one wakes them.

Each step is the program span `engine.step`, with `engine.admit` (per
admitted request: its id and its wait in the queue), `engine.prefill`
and `engine.splice` inside it, then `engine.decode` (active and total
slots), `engine.tokens` (the per-slot token reads and retirements) and
`engine.core_manager` (each call into the core manager); see
`repro.telemetry.spans`.

For an MoE model the prefill and decode programs also accumulate, on
the device, the routing counts of the experts the model holds: tokens
routed to each held expert (prefill and decode apart) and non-empty
(step, layer, held expert) groups. Nothing reads them per step;
`moe_counts()` reads them once, on request, and exports them through
the telemetry hub as `engine/moe_expert_tokens` and
`engine/moe_expert_groups`.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CoreManager, CorePolicy
from repro.models import Model
from repro.sim.tasks import TaskIdAllocator
from repro.telemetry.spans import span


@dataclasses.dataclass
class Request:
    req_id: int
    prompt: list[int]
    max_new_tokens: int
    output: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    submit_s: float = 0.0       # engine clock at `submit`


class InferenceEngine:
    def __init__(self, model: Model, params, max_batch: int = 8,
                 max_len: int = 256,
                 policy: CorePolicy | str = "proposed",
                 num_host_cores: int = 16,
                 eos_id: int | None = None,
                 clock: Callable[[], float] = time.monotonic,
                 greedy: bool = True, temperature: float = 1.0,
                 sample_seed: int = 0, telemetry=None):
        cfg = model.cfg
        if cfg.family in ("hybrid", "audio") or cfg.is_encdec:
            raise NotImplementedError(
                "engine batching supports decoder-only families "
                "(dense/moe/vlm/ssm); use Model.decode_step directly")
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.greedy = greedy
        self.temperature = temperature
        self._sample_key = jax.random.key(sample_seed)
        self.clock = clock
        self._t0 = clock()
        # Live serving shares the simulator's telemetry surface: the same
        # hub type, the same probes, exported via `prometheus_text()` —
        # first step toward running the simulator as a digital twin.
        self.telemetry = telemetry
        self._queue_wait = self._active_slots = None
        if telemetry is not None:
            self._queue_wait = telemetry.get_series("engine/queue_wait_s")
            self._active_slots = telemetry.get_series("engine/active_slots")
        self.core_manager = CoreManager(num_host_cores, policy=policy,
                                        rng=np.random.default_rng(0),
                                        telemetry=telemetry)
        self._task_ids = TaskIdAllocator()   # per-engine CPU-task id stream
        self._last_idle_check = 0.0

        self.slots: list[Request | None] = [None] * max_batch
        self.pending: list[Request] = []
        self.cache = self._empty_cache()
        self.tokens = jnp.zeros((max_batch, 1), jnp.int32)
        self.active_mask = np.zeros(max_batch, bool)
        self._decode = jax.jit(self._decode_fn)
        self._prefill = jax.jit(self._prefill_fn, static_argnums=(2,))
        self._next_id = 0

    # ------------------------- device functions ------------------------ #
    def _empty_cache(self):
        cfg = self.model.cfg
        b, s = self.max_batch, self.max_len

        def fn(p, t):
            _, cache = self.model.prefill(p, t, None, max_len=s)
            return cache
        abstract = jax.eval_shape(
            fn, self.model.abstract_params(),
            jax.ShapeDtypeStruct((b, 1), jnp.int32))
        cache = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), abstract)
        cache["pos"] = jnp.zeros((b,), jnp.int32)
        if "moe" in cache:
            # accumulated routing counts, row 0 prefill and row 1 decode
            cache["moe"] = jax.tree.map(
                lambda a: jnp.zeros((2, *a.shape), a.dtype), cache["moe"])
        return cache

    @staticmethod
    def _count(total, counts, phase: int):
        """Add one call's routing counts to row `phase` (0 prefill,
        1 decode) of the accumulated counts."""
        return jax.tree.map(lambda t, c: t.at[phase].add(c), total, counts)

    def _prefill_fn(self, params, tokens, max_len, moe=None):
        """The prompt's logits and cache; with the engine's accumulated
        routing counts `moe`, the cache's `moe` is them plus this
        prefill's."""
        logits, cache = self.model.prefill(params, tokens, None,
                                           max_len=max_len)
        if moe is not None:
            cache["moe"] = self._count(moe, cache["moe"], 0)
        return logits, cache

    def _decode_fn(self, params, cache, tokens, active):
        logits, new_cache = self.model.decode_step(params, cache, tokens,
                                                   active)
        # inactive slots must not advance their position
        new_cache["pos"] = jnp.where(active, new_cache["pos"], cache["pos"])
        if "moe" in cache:
            new_cache["moe"] = self._count(cache["moe"], new_cache["moe"], 1)
        return logits, new_cache

    # ----------------------------- host API ---------------------------- #
    def _now(self) -> float:
        return self.clock() - self._t0

    def _cpu_task(self, name: str) -> None:
        """Account one Table-2 host task against the core manager."""
        with span("engine.core_manager"):
            task = self._task_ids.new(name)
            t = self._now()
            self.core_manager.assign(task.task_id, t)
            self.core_manager.release(task.task_id, t + task.duration_s)

    def _periodic(self) -> None:
        with span("engine.core_manager"):
            t = self._now()
            if t - self._last_idle_check >= \
                    self.core_manager.idling_period_s:
                self.core_manager.periodic(t)
                self._last_idle_check = t

    def submit(self, prompt: list[int], max_new_tokens: int = 32) -> int:
        self._cpu_task("submit")
        req = Request(self._next_id, list(prompt), max_new_tokens,
                      submit_s=self._now())
        self._next_id += 1
        self.pending.append(req)
        return req.req_id

    def _admit(self) -> None:
        for i in range(self.max_batch):
            if self.slots[i] is not None or not self.pending:
                continue
            req = self.pending.pop(0)
            now = self._now()
            wait = now - req.submit_s
            if self._queue_wait is not None:
                self._queue_wait.observe(now, wait)
            with span("engine.admit", req_id=req.req_id,
                      wait_ms=wait * 1e3):
                self._admit_one(i, req)

    def _admit_one(self, i: int, req: Request) -> None:
        """Prefill `req` into slot `i` and read its first token."""
        self._cpu_task("alloc_memory")
        with span("engine.prefill"):
            toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
            logits, pcache = self._prefill(self.params, toks, self.max_len,
                                           self.cache.get("moe"))

        # splice the single-row prefill cache into slot i
        def splice(big, small):
            if small.ndim == 0:
                return big
            return big.at[:, i].set(small[:, 0])
        with span("engine.splice"):
            new_cache = {}
            for key in self.cache:
                if key == "pos":
                    new_cache[key] = self.cache[key].at[i].set(
                        len(req.prompt))
                elif key == "moe":      # counts, not a row: the prefill's
                    new_cache[key] = pcache[key]
                else:
                    new_cache[key] = jax.tree.map(
                        splice, self.cache[key], pcache[key])
            self.cache = new_cache
        first = self._select_token(logits[:, -1])
        req.output.append(int(first[0]))
        self.tokens = self.tokens.at[i, 0].set(first[0])
        self.slots[i] = req
        self.active_mask[i] = True

    def _select_token(self, logits_row: jax.Array) -> jax.Array:
        v = self.model.cfg.vocab_size
        logits_row = logits_row[..., :v]
        if self.greedy:
            return jnp.argmax(logits_row, -1).astype(jnp.int32)
        self._sample_key, sub = jax.random.split(self._sample_key)
        return jax.random.categorical(
            sub, logits_row / self.temperature, -1).astype(jnp.int32)

    def step(self) -> list[tuple[int, int]]:
        """One engine iteration: admit pending, batched decode, retire
        finished. Returns [(req_id, new_token), ...]."""
        with span("engine.step"):
            return self._step()

    def _step(self) -> list[tuple[int, int]]:
        self._periodic()
        self._admit()
        n_active = int(self.active_mask.sum())
        if not n_active:
            return []
        self._cpu_task("start_iteration")
        if self._active_slots is not None:
            self._active_slots.observe(self._now(), n_active)
        with span("engine.decode", active=n_active, slots=self.max_batch):
            active = jnp.asarray(self.active_mask)
            logits, self.cache = self._decode(self.params, self.cache,
                                              self.tokens, active)
            new_tokens = self._select_token(logits[:, 0])
        out = []
        with span("engine.tokens"):
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                tok = int(new_tokens[i])
                req.output.append(tok)
                out.append((req.req_id, tok))
                self.tokens = self.tokens.at[i, 0].set(tok)
                hit_eos = self.eos_id is not None and tok == self.eos_id
                if len(req.output) >= req.max_new_tokens or hit_eos:
                    req.done = True
                    self._cpu_task("finish_request")
                    self._cpu_task("free_memory")
                    self.slots[i] = None
                    self.active_mask[i] = False
        return out

    def run_until_drained(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.pending and not self.active_mask.any():
                return
            self.step()
        raise RuntimeError("engine did not drain")

    # -------------------------- observability -------------------------- #
    def moe_counts(self) -> dict | None:
        """The held experts' routing counts so far, read from the device
        once: `tokens` (2, held experts) and `groups` (2,), row 0 for
        prefill and row 1 for decode. With a telemetry hub they are also
        recorded as the timelines `engine/moe_expert_tokens` (prefill
        then decode tokens per held expert) and `engine/moe_expert_groups`.
        None for a model without experts."""
        if "moe" not in self.cache:
            return None
        counts = {k: np.asarray(v) for k, v in jax.device_get(
            self.cache["moe"]).items()}
        if self.telemetry is not None:
            t = self._now()
            self.telemetry.timeline("engine/moe_expert_tokens").record(
                t, counts["tokens"].ravel().tolist())
            self.telemetry.timeline("engine/moe_expert_groups").record(
                t, counts["groups"].tolist())
        return counts

    def host_cpu_report(self) -> dict:
        m = self.core_manager
        return {
            "policy": m.policy_name,
            "frequencies": m.frequencies(self._now()).tolist(),
            "cv": m.frequency_cv(),
            "mean_degradation": m.mean_frequency_degradation(),
            "active_cores": int((m.c_state == 0).sum()),
            "assigns": m.metrics.assigns,
        }

    def prometheus_text(self) -> str:
        """Prometheus-style text snapshot of the engine's host CPU —
        the telemetry hub's probes (when one is attached) plus live
        aging gauges, one metrics surface shared with the simulator's
        exports (`repro.telemetry.prometheus_text`). Serve it with
        `repro.telemetry.start_metrics_server(engine.prometheus_text)`.
        """
        from repro.telemetry import TelemetryHub, prometheus_text
        hub = self.telemetry if self.telemetry is not None \
            else TelemetryHub()
        m = self.core_manager
        extra = {
            "host_freq_cv": m.frequency_cv(self._now()),
            "host_mean_degradation": m.mean_frequency_degradation(),
            "host_active_cores": float((m.c_state == 0).sum()),
            "host_assigns": float(m.metrics.assigns),
            "host_oversub_assigns": float(m.metrics.oversub_assigns),
        }
        return prometheus_text(hub, extra_gauges=extra)
