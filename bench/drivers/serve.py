"""Runs serving cells: an open-loop request stream into the
program's continuous-batching engine, `repro.serving.InferenceEngine`,
built as `repro.launch.serve` builds it.

Set-up draws the weights on the device from the seed
(`bench/weights.py`), builds the engine, and warms every prompt length
the cell's traffic uses, and every batch slot, through the engine's own
`submit`/`step`. The window's schedule (the traffic file's arrival
rate; sizes and gaps from `bench/generate.py`'s stratified draw) is
then played `preroll_periods` times before the window opens, as part
of set-up, so that the window starts with the engine as busy as the
traffic keeps it. In the window each request is submitted when it is
due and the engine is stepped; a request is timed from when it was
due. When the window closes, the requests still in flight are drained.

The end-to-end metrics: time to first token over the requests due in
the window, the gaps between consecutive output tokens that end in the
window (of every request, the pre-roll's too), and the output tokens
emitted in the window over its length.

`correct`: once the engine is freed, a sample of the window's finished
requests drawn from the seed, the one with the most output tokens
among them, is run through the float32 reference
(`bench/reference/minicpm3.py`) over its prompt and served tokens, and
the widest gap by which a served token's reference logit lies below
the reference's best at that position is compared with its limit.
"""
from __future__ import annotations

import gc
import json
import time
from pathlib import Path

import numpy as np

from bench import generate

LIMITS_DIR = Path(__file__).resolve().parents[1] / "limits"
DRAIN_LIMIT_S = 150.0
# the percentiles that BENCHMARK.json names, end to end and per layer
TTFT_PERCENTILES = (50, 90)
TBT_PERCENTILES = (50, 95, 99)


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def program_config(config: dict):
    from repro.configs import get_config, get_smoke_config
    get = get_smoke_config if config.get("smoke") else get_config
    cfg = get(config["program_config"])
    m = config["model"]
    stated = {"d_model": m["hidden_size"], "num_layers": m["num_hidden_layers"],
              "num_heads": m["num_attention_heads"], "d_ff": m["intermediate_size"],
              "vocab_size": m["vocab_size"], "q_lora_rank": m["q_lora_rank"],
              "kv_lora_rank": m["kv_lora_rank"], "qk_nope_dim": m["qk_nope_head_dim"],
              "qk_rope_dim": m["qk_rope_head_dim"], "v_head_dim": m["v_head_dim"],
              "norm_eps": m["rms_norm_eps"], "rope_theta": m["rope_theta"],
              "attn_type": "mla", "dtype": config["serving"]["dtype"]}
    differs = {k: (getattr(cfg, k), v) for k, v in stated.items()
               if getattr(cfg, k) != v}
    if differs:
        raise ValueError(f"the program's {cfg.name} departs from the "
                         f"configuration file: {differs}")
    return cfg


class Server:
    """The engine under test, built and warmed."""

    def __init__(self, config: dict, seed: int, prompt_lengths):
        import jax
        from repro.launch.serve import check_fits
        from repro.models import Model
        from repro.serving.engine import InferenceEngine

        from bench import weights

        s = config["serving"]
        self.m = config["model"]
        self.cfg = program_config(config)
        model = Model(self.cfg)
        check_fits(model, s["max_batch"], s["max_len"])
        params = weights.program_params(jax.random.key(seed), self.m,
                                        self.cfg.padded_vocab,
                                        model.abstract_params())
        jax.block_until_ready(params)
        self.engine = InferenceEngine(
            model, params, max_batch=s["max_batch"], max_len=s["max_len"],
            policy=s["policy"], num_host_cores=s["host_cores"])
        self.vocab = self.cfg.vocab_size
        # every prompt length once, then enough short ones to fill every
        # slot: each prefill shape and each slot's splice compiles here
        rng = np.random.default_rng(seed)
        lengths = sorted(set(prompt_lengths))
        lengths += [lengths[0]] * max(0, s["max_batch"] - len(lengths))
        for n in lengths:
            self.engine.submit(rng.integers(0, self.vocab, n).tolist(), 2)
        self.engine.run_until_drained()
        jax.block_until_ready(self.engine.cache)

    def free(self) -> None:
        import jax
        del self.engine
        gc.collect()
        jax.clear_caches()


def schedule(traffic: dict, seconds: float, seed: int, vocab: int):
    """(pre-roll, window) request lists. The window's requests are due in
    [0, seconds); the pre-roll plays the same schedule
    `preroll_periods` times before it (due times below 0). Prompt token
    ids are drawn from the seed for each request."""
    window = generate.stratified(traffic, seconds, seed)
    preroll = [dict(r, due_s=r["due_s"] - k * seconds)
               for k in range(int(traffic.get("preroll_periods", 0)), 0, -1)
               for r in window]
    rng = np.random.default_rng([seed, 1])
    for r in preroll + window:
        r["prompt"] = rng.integers(0, vocab, r["input_tokens"]).tolist()
    return preroll, window


def _record(live: dict, t: float) -> list:
    """Note the output tokens the last step gave each live request, at
    time `t`; drop finished requests. Returns [(request, token index)]."""
    emitted = []
    for rid, r in list(live.items()):
        obj = r["obj"]
        while len(r["token_s"]) < len(obj.output):
            emitted.append((r, len(r["token_s"])))
            r["token_s"].append(t)
        if obj.done:
            del live[rid]
    return emitted


def open_loop(engine, reqs: list[dict], origin: float, until: float,
              live: dict, span=None):
    """Submit each of `reqs` when due (seconds from `origin`, a
    `time.perf_counter()` reading) and step the engine until `until`
    seconds from `origin`. Each submitted request joins `live` and gets
    `obj` (the engine's Request) and `token_s` (the time of each output
    token, from `origin`). Returns (end, per-step records
    (time, [(request, token index)]), lag of each submission behind
    its due time)."""
    import contextlib
    span = span or (lambda name: contextlib.nullcontext())
    steps, nxt, late = [], 0, []
    while True:
        now = time.perf_counter() - origin
        if now >= until:
            break
        while nxt < len(reqs) and reqs[nxt]["due_s"] <= now:
            r = reqs[nxt]
            engine.submit(r["prompt"], max_new_tokens=r["output_tokens"])
            r["obj"] = engine.pending[-1]
            r["token_s"] = []
            late.append(now - r["due_s"])
            live[r["obj"].req_id] = r
            nxt += 1
        if not engine.pending and not engine.active_mask.any():
            due = reqs[nxt]["due_s"] if nxt < len(reqs) else until
            with span("wait"):
                time.sleep(max(0.0, min(due, until) - now))
            continue
        with span("step"):
            engine.step()
        t = time.perf_counter() - origin
        steps.append((t, _record(live, t)))
    return time.perf_counter() - origin, steps, late


def play_preroll(engine, preroll: list[dict]):
    """Play the pre-roll in open loop, timed so that the window opens
    when it ends. Returns (the window's origin, the live requests)."""
    live = {}
    origin = time.perf_counter() - min((r["due_s"] for r in preroll),
                                       default=0.0)
    open_loop(engine, preroll, origin, 0.0, live)
    return origin, live


def drain(engine, live: dict, origin: float, limit_s: float) -> None:
    """Step until every submitted request has finished (or the limit)."""
    t_end = time.perf_counter() + limit_s
    while (engine.pending or engine.active_mask.any()) \
            and time.perf_counter() < t_end:
        engine.step()
        _record(live, time.perf_counter() - origin)


def latency_metrics(window_reqs, all_reqs, window_s: float) -> dict:
    """Time to first token of each request due in the window (infinite
    for one that never got it), the gaps between consecutive output
    tokens that end in the window, and the output tokens emitted in it
    per second."""
    ttft = [(r["token_s"][0] - r["due_s"]) * 1e3 if r.get("token_s") else
            float("inf") for r in window_reqs]
    gaps = [(b - a) * 1e3 for r in all_reqs
            for a, b in zip(r.get("token_s", []), r.get("token_s", [])[1:])
            if 0.0 < b <= window_s]
    tokens = sum(1 for r in all_reqs for t in r.get("token_s", [])
                 if 0.0 < t <= window_s)
    out = {"ttft_ms": ttft, "tbt_ms": gaps,
           "serve_output_tok_per_s": tokens / window_s}
    for q in TTFT_PERCENTILES:
        out[f"serve_ttft_p{q}_ms"] = percentile(ttft, q)
    for q in TBT_PERCENTILES:
        out[f"serve_tbt_p{q}_ms"] = percentile(gaps, q)
    return out


def sample_for_reference(reqs, seed: int, min_tokens: int, max_requests: int):
    """Finished requests drawn from the seed: the one with the most
    output tokens first, then others in a seeded order until the sample
    holds `min_tokens` served tokens."""
    done = [r for r in reqs if r.get("obj") is not None and r["obj"].done]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["obj"].output))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    out, n = [longest], len(longest["obj"].output)
    for i in order:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i]["obj"].output)
    return out


def served_gaps(seed: int, m: dict, padded_vocab: int, max_len: int,
                sample, fp8: bool = False) -> list[float]:
    """Per request of `sample`, the widest gap (reference best logit
    minus the reference logit of the served token) over its served
    tokens. With `fp8`, the token read at each position is the one the
    fp8 control ranks first instead of the served one."""
    import jax.numpy as jnp

    from bench.reference import minicpm3 as ref
    gaps = []
    for r in sample:
        prompt, out = r["prompt"], list(r["obj"].output)
        seq = prompt + out[:-1]
        tokens = np.zeros(max_len, np.int32)
        tokens[: len(seq)] = seq
        # positions read, padded to a power of two (repeating the last)
        # so that the reference compiles for a few shapes only
        n = len(out)
        read = np.arange(len(prompt) - 1, len(seq))
        read = np.pad(read, (0, max(64, 1 << (n - 1).bit_length()) - n),
                      mode="edge")
        lg = ref.logits(seed, m, padded_vocab, tokens, read)[:n]
        best = jnp.max(lg, -1)
        if fp8:
            chosen = jnp.argmax(ref.logits(seed, m, padded_vocab, tokens,
                                           read, fp8=True)[:n], -1)
        else:
            chosen = jnp.asarray(out, jnp.int32)
        picked = jnp.take_along_axis(lg, chosen[:, None], -1)[:, 0]
        gaps.append(float(jnp.max(best - picked)))
    return gaps


def run(spec: dict, *, seed: int, seconds: float, trace: bool,
        t_start: float | None = None) -> dict:
    from bench.run import memory_peak_bytes

    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    limits = json.loads((LIMITS_DIR / f"{cell['name']}.json").read_text())
    s = config["serving"]
    t0 = time.perf_counter() if t_start is None else t_start
    preroll, reqs = schedule(traffic, seconds, seed,
                             config["model"]["vocab_size"])
    server = Server(config, seed, [r["input_tokens"] for r in reqs])
    origin, live = play_preroll(server.engine, preroll)
    setup_s = time.perf_counter() - t0
    print(f"serve: set-up {setup_s:.3f} s ({len(preroll)} pre-roll "
          f"requests, {len(live)} in flight); {len(reqs)} requests due in "
          f"{seconds:g} s", flush=True)

    observations = None
    if trace:
        import tempfile

        from bench import trace as trace_mod
        log_dir = tempfile.mkdtemp(prefix="serve-trace-")
        with trace_mod.capture(log_dir):
            with trace_mod.span("window"):
                window_s, steps, late = open_loop(
                    server.engine, reqs, origin, seconds, live,
                    span=trace_mod.span)
    else:
        window_s, steps, late = open_loop(server.engine, reqs, origin,
                                          seconds, live)
    drain(server.engine, live, origin, DRAIN_LIMIT_S)
    peak = memory_peak_bytes()
    mets = latency_metrics(reqs, preroll + reqs, window_s)
    failed = sum(1 for r in reqs
                 if "obj" not in r or not r["obj"].done
                 or len(r["obj"].output) != r["output_tokens"])
    named = "; ".join(f"{k} {v:.2f}" for k, v in mets.items()
                      if k.startswith("serve_"))
    print(f"serve: window {window_s:.3f} s, {len(steps)} steps, "
          f"{len(reqs)} requests, {failed} failed, {len(live)} unfinished "
          f"after the drain, submission lag max "
          f"{max(late, default=0.0) * 1e3:.1f} ms, "
          f"{len(mets['tbt_ms'])} gaps; {named}", flush=True)
    if trace:
        observations = _observations(config, steps, log_dir)
        observations["latency"] = {k: v for k, v in mets.items()
                                   if k.startswith("serve_")}
    server.free()

    t1 = time.perf_counter()
    sample = sample_for_reference(reqs, seed, traffic["check_tokens"],
                                  traffic["check_requests"])
    gaps = served_gaps(seed, config["model"], server.cfg.padded_vocab,
                       s["max_len"], sample)
    print(f"serve: reference over {len(sample)} requests / "
          f"{sum(len(r['obj'].output) for r in sample)} served tokens in "
          f"{time.perf_counter() - t1:.3f} s; widest gap per request "
          f"{gaps}", flush=True)
    checks = [{"name": "served_logit_gap", "value": max(gaps, default=float("inf")),
               "limit": limits["served_logit_gap"]}]
    correct = bool(sample) and all(c["value"] <= c["limit"] for c in checks)
    out = {"correct": correct, "attempted": len(reqs), "failed": failed,
           "checks": checks, "memory_peak_bytes": peak,
           "metrics": {"setup_s": setup_s,
                       **{k: v for k, v in mets.items()
                          if k.startswith("serve_")}}}
    if observations is not None:
        out["observations"] = observations
    return out


def _observations(config, steps, log_dir) -> dict:
    """What the per-layer readers need from a traced window: the trace,
    and the model work of the prefills and decode steps it holds."""
    import shutil

    from bench import trace as trace_mod
    t1 = time.perf_counter()
    summary = trace_mod.reduce(trace_mod.latest_xplane(log_dir))
    shutil.rmtree(log_dir, ignore_errors=True)
    print(f"serve: trace reduced in {time.perf_counter() - t1:.3f} s; "
          f"window {summary.window_s:.3f} s, busy {summary.busy_s:.3f} s; "
          f"programs {trace_mod.top(summary.modules_s)}", flush=True)
    prompts, contexts = [], []
    for _, emitted in steps:
        ctx = []
        for r, j in emitted:
            if j == 0:
                prompts.append(r["input_tokens"])
            else:
                ctx.append(r["input_tokens"] + j)
        if ctx:
            contexts.append(ctx)
    return {"trace": summary, "model": config["model"],
            "prefill_tokens": prompts, "decode_contexts": contexts}
