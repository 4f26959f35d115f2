"""Runs serving cells of a DeepSeek-V2-style MoE model: the open loop of
`bench/drivers/serve.py` (its functions, by import) into the program's
`repro.serving.InferenceEngine`, with the model's weights
(`bench/weights_moe.py`), reference (`bench/reference/deepseek_v2_lite.py`)
and work counts (`bench/flops_moe.py`).

The configuration holds one chip's share of a stated deployment: the
router scores every routed expert (`share.router_experts`) and the
chip holds `n_routed_experts` of them from `share.expert_offset`.
Set-up, window, drain and the end-to-end metrics are those of
`serve.py`. A traced window also hands the readers the engine's routing
counts of the held experts, read from the device once when the window
opens and once when it closes (`InferenceEngine.moe_counts`).

`correct`: as in `serve.py`, the widest gap by which a served token's
float32 reference logit lies below the reference's best, over a sample
of the window's finished requests drawn from the seed.

Run directly, it takes the readings the limit of `correct` is set from
(the program's, and the float8 control's on the first seeds), or, with
`--sweep`, the cell's steady-state latency and throughput at ascending
offered rates, each from its own pre-roll, over `--seeds` seeds (and
the control's reading on each seed's first window):

    python bench/drivers/serve_moe.py --workload <cell> --seeds 6 \
        [--control-seeds 3] [--seconds 20] [--sweep 0.6,0.7,0.8]
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import numpy as np  # noqa: E402

from bench.drivers import serve  # noqa: E402

LIMITS_DIR = Path(__file__).resolve().parents[1] / "limits"


# the configuration file's own keys; every other top-level key is the
# model's published config (Hugging Face keys), as the chip's share cuts it
FILE_KEYS = frozenset({"driver", "source", "program_config", "deployment",
                       "share", "published", "serving", "precision",
                       "reduced", "not_modelled", "assumed", "smoke"})


def model_dict(config: dict) -> dict:
    """The model's published config with the share's router width and
    offset."""
    share = config["share"]
    return dict({k: v for k, v in config.items() if k not in FILE_KEYS},
                router_experts=share["router_experts"],
                expert_offset=share["expert_offset"])


def program_config(config: dict):
    """The program's ModelConfig, checked against the configuration."""
    from repro.configs import get_config, get_smoke_config
    get = get_smoke_config if config.get("smoke") else get_config
    cfg = get(config["program_config"])
    m = model_dict(config)
    ys = m["rope_scaling"]
    stated = {
        "d_model": m["hidden_size"], "num_layers": m["num_hidden_layers"],
        "num_heads": m["num_attention_heads"], "d_ff": m["intermediate_size"],
        "vocab_size": m["vocab_size"], "q_lora_rank": m["q_lora_rank"] or 0,
        "kv_lora_rank": m["kv_lora_rank"], "qk_nope_dim": m["qk_nope_head_dim"],
        "qk_rope_dim": m["qk_rope_head_dim"], "v_head_dim": m["v_head_dim"],
        "norm_eps": m["rms_norm_eps"], "rope_theta": m["rope_theta"],
        "yarn_factor": ys["factor"],
        "yarn_original_max_pos": ys["original_max_position_embeddings"],
        "yarn_beta_fast": ys["beta_fast"], "yarn_beta_slow": ys["beta_slow"],
        "yarn_mscale": ys["mscale"], "yarn_mscale_all_dim": ys["mscale_all_dim"],
        "num_experts": m["n_routed_experts"],
        "router_experts": m["router_experts"],
        "expert_offset": m["expert_offset"],
        "experts_per_token": m["num_experts_per_tok"],
        "expert_d_ff": m["moe_intermediate_size"],
        "shared_experts": m["n_shared_experts"],
        "first_dense_layers": m["first_k_dense_replace"],
        "moe_renormalize": m["norm_topk_prob"],
        "moe_gate_scale": m["routed_scaling_factor"],
        "attn_type": "mla", "dtype": config["serving"]["dtype"]}
    differs = {k: (getattr(cfg, k), v) for k, v in stated.items()
               if getattr(cfg, k) != v}
    if differs:
        raise ValueError(f"the program's {cfg.name} departs from the "
                         f"configuration file: {differs}")
    return cfg


class Server(serve.Server):
    """The engine under test, built and warmed as `serve.Server` does,
    with this model's weights."""

    def __init__(self, config: dict, seed: int, prompt_lengths):
        import jax
        from repro.launch.serve import check_fits
        from repro.models import Model
        from repro.serving.engine import InferenceEngine

        from bench import weights_moe

        s = config["serving"]
        self.m = model_dict(config)
        self.cfg = program_config(config)
        model = Model(self.cfg)
        check_fits(model, s["max_batch"], s["max_len"])
        params = weights_moe.program_params(
            jax.random.key(seed), self.m, self.cfg.padded_vocab,
            model.abstract_params())
        jax.block_until_ready(params)
        self.engine = InferenceEngine(
            model, params, max_batch=s["max_batch"], max_len=s["max_len"],
            policy=s["policy"], num_host_cores=s["host_cores"])
        self.vocab = self.cfg.vocab_size
        rng = np.random.default_rng(seed)
        lengths = sorted(set(prompt_lengths))
        lengths += [lengths[0]] * max(0, s["max_batch"] - len(lengths))
        for n in lengths:
            self.engine.submit(rng.integers(0, self.vocab, n).tolist(), 2)
        self.engine.run_until_drained()
        jax.block_until_ready(self.engine.cache)


def served_gaps(seed: int, m: dict, padded_vocab: int, max_len: int,
                sample, fp8: bool = False) -> list[float]:
    """`serve.served_gaps` with this model's reference."""
    import jax.numpy as jnp

    from bench.reference import deepseek_v2_lite as ref
    gaps = []
    for r in sample:
        prompt, out = r["prompt"], list(r["obj"].output)
        seq = prompt + out[:-1]
        tokens = np.zeros(max_len, np.int32)
        tokens[: len(seq)] = seq
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


def _counts_diff(after: dict, before: dict) -> dict:
    """Per phase (prefill, decode): routed tokens and non-empty groups
    between two reads of the engine's counts."""
    return {"tokens": (after["tokens"] - before["tokens"]).sum(1).tolist(),
            "groups": (after["groups"] - before["groups"]).tolist()}


def run(spec: dict, *, seed: int, seconds: float, trace: bool,
        t_start: float | None = None) -> dict:
    from bench.run import memory_peak_bytes

    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    limits = json.loads((LIMITS_DIR / f"{cell['name']}.json").read_text())
    s = config["serving"]
    t0 = time.perf_counter() if t_start is None else t_start
    preroll, reqs = serve.schedule(traffic, seconds, seed,
                                   config["vocab_size"])
    server = Server(config, seed, [r["input_tokens"] for r in reqs])
    origin, live = serve.play_preroll(server.engine, preroll)
    setup_s = time.perf_counter() - t0
    print(f"serve_moe: set-up {setup_s:.3f} s ({len(preroll)} pre-roll "
          f"requests, {len(live)} in flight); {len(reqs)} requests due in "
          f"{seconds:g} s", flush=True)

    observations = None
    if trace:
        import tempfile

        from bench import trace as trace_mod
        log_dir = tempfile.mkdtemp(prefix="serve-moe-trace-")
        before = server.engine.moe_counts()
        with trace_mod.capture(log_dir):
            with trace_mod.span("window"):
                window_s, steps, late = serve.open_loop(
                    server.engine, reqs, origin, seconds, live,
                    span=trace_mod.span)
        moe = _counts_diff(server.engine.moe_counts(), before)
    else:
        window_s, steps, late = serve.open_loop(server.engine, reqs, origin,
                                                seconds, live)
    serve.drain(server.engine, live, origin, serve.DRAIN_LIMIT_S)
    peak = memory_peak_bytes()
    mets = serve.latency_metrics(reqs, preroll + reqs, window_s)
    failed = sum(1 for r in reqs
                 if "obj" not in r or not r["obj"].done
                 or len(r["obj"].output) != r["output_tokens"])
    named = "; ".join(f"{k} {v:.2f}" for k, v in mets.items()
                      if k.startswith("serve_"))
    print(f"serve_moe: window {window_s:.3f} s, {len(steps)} steps, "
          f"{len(reqs)} requests, {failed} failed, {len(live)} unfinished "
          f"after the drain, submission lag max "
          f"{max(late, default=0.0) * 1e3:.1f} ms, "
          f"{len(mets['tbt_ms'])} gaps; {named}; memory peak {peak} B",
          flush=True)
    if trace:
        observations = serve._observations(dict(config, model=server.m),
                                           steps, log_dir)
        observations["moe"] = moe
        observations["latency"] = {k: v for k, v in mets.items()
                                   if k.startswith("serve_")}
        print(f"serve_moe: routing in the window {moe}", flush=True)
    padded = server.cfg.padded_vocab
    server.free()

    t1 = time.perf_counter()
    sample = serve.sample_for_reference(reqs, seed, traffic["check_tokens"],
                                        traffic["check_requests"])
    gaps = served_gaps(seed, model_dict(config), padded, s["max_len"],
                       sample)
    print(f"serve_moe: reference over {len(sample)} requests / "
          f"{sum(len(r['obj'].output) for r in sample)} served tokens in "
          f"{time.perf_counter() - t1:.3f} s; widest gap per request "
          f"{gaps}", flush=True)
    checks = [{"name": "served_logit_gap",
               "value": max(gaps, default=float("inf")),
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


# ------------------------- calibration and sweep ------------------------ #
def readings(spec: dict, seed: int, control: bool, seconds: float) -> dict:
    """What a sound run compares (`program`) and, with `control`, what the
    float8 control reads over the same requests."""
    config, traffic = spec["config"], spec["traffic"]
    s = config["serving"]
    preroll, reqs = serve.schedule(traffic, seconds, seed,
                                   config["vocab_size"])
    server = Server(config, seed, [r["input_tokens"] for r in reqs])
    origin, live = serve.play_preroll(server.engine, preroll)
    serve.open_loop(server.engine, reqs, origin, seconds, live)
    serve.drain(server.engine, live, origin, serve.DRAIN_LIMIT_S)
    padded = server.cfg.padded_vocab
    server.free()
    sample = serve.sample_for_reference(reqs, seed, traffic["check_tokens"],
                                        traffic["check_requests"])
    m = model_dict(config)
    row = {"seed": seed, "requests": len(sample),
           "served_tokens": sum(len(r["obj"].output) for r in sample),
           "program": {"served_logit_gap": max(served_gaps(
               seed, m, padded, s["max_len"], sample))}}
    if control:
        row["control"] = {"served_logit_gap": max(served_gaps(
            seed, m, padded, s["max_len"], sample, fp8=True))}
    return row


SETTLE_LIMIT_S = 30.0
# a rate is past the knee once the output tokens fall this far below the
# offered ones, or the median time to first token grows this many times
# over the lowest rate's
TRACKS = 0.9
TTFT_GROWTH = 5.0


def _abandon(engine) -> None:
    """Drop every queued and in-flight request, so that the next rate
    starts from an idle engine without a drain."""
    engine.pending.clear()
    engine.slots = [None] * engine.max_batch
    engine.active_mask[:] = False


def sweep_point(server, spec: dict, rate: float, seconds: float,
                seed: int):
    """The cell at one offered rate, in steady state: its schedule at
    `rate`, pre-roll included, from an idle engine; after the window the
    engine steps until every window request has its first token (or
    SETTLE_LIMIT_S) and the rest is abandoned. Returns (the readings,
    the window's requests)."""
    config, traffic = spec["config"], spec["traffic"]
    t = dict(traffic, arrival=dict(traffic["arrival"], rate_rps=rate))
    preroll, reqs = serve.schedule(t, seconds, seed,
                                   config["vocab_size"])
    engine = server.engine
    origin, live = serve.play_preroll(engine, preroll)
    before = engine.moe_counts()
    window_s, steps, _ = serve.open_loop(engine, reqs, origin, seconds, live)
    moe = _counts_diff(engine.moe_counts(), before)
    t_end = time.perf_counter() + SETTLE_LIMIT_S
    while any(not r.get("token_s") for r in reqs) \
            and time.perf_counter() < t_end:
        engine.step()
        serve._record(live, time.perf_counter() - origin)
    _abandon(engine)
    mets = serve.latency_metrics(reqs, preroll + reqs, window_s)
    offered = sum(r["output_tokens"] for r in reqs) / seconds
    decode_rows = sum(1 for _, emitted in steps for _, j in emitted if j)
    row = {"seed": seed, "rate_rps": rate, "requests": len(reqs),
           "steps": len(steps), "offered_tok_per_s": offered,
           "tracks": mets["serve_output_tok_per_s"] / offered,
           "live_slots_mean": decode_rows / max(len(steps), 1),
           "expert_tokens_per_step": moe["tokens"][1] / max(len(steps), 1)
           / server.cfg.num_experts
           / (server.cfg.num_layers - server.cfg.first_dense_layers),
           **{k: v for k, v in mets.items() if k.startswith("serve_")}}
    return row, reqs


def sweep(spec: dict, rates, seconds: float, seeds, control: bool = True,
          on_row=print) -> list[dict]:
    """The cell's steady-state readings at ascending offered rates
    (`sweep_point`), each seed on its own weights. The first seed runs
    `rates` until the first one past the knee; the others run from one
    step below the first seed's knee to that point. With `control`, the
    program's and the float8 control's `served_logit_gap` are read on
    each seed's first window."""
    config, traffic = spec["config"], spec["traffic"]
    mix = traffic["mix"]
    rates = sorted(rates)
    rows, todo = [], rates
    for k, seed in enumerate(seeds):
        server = Server(config, seed, range(mix["input_round"],
                                            mix["input_max"] + 1,
                                            mix["input_round"]))
        sample, first = None, None
        for rate in todo:
            row, reqs = sweep_point(server, spec, rate, seconds, seed)
            first = first or row
            row["past_knee"] = (
                row["tracks"] < TRACKS or row["serve_ttft_p50_ms"]
                > TTFT_GROWTH * first["serve_ttft_p50_ms"])
            rows.append(row)
            on_row(json.dumps(row))
            if sample is None:
                sample = serve.sample_for_reference(
                    reqs, seed, traffic["check_tokens"],
                    traffic["check_requests"])
            if row["past_knee"]:
                break
        if k == 0:
            i = rates.index(rows[-1]["rate_rps"])
            todo = rates[max(0, i - 2): i + 1]
        padded = server.cfg.padded_vocab
        server.free()
        if control and sample:
            m = model_dict(config)
            max_len = config["serving"]["max_len"]
            on_row(json.dumps({
                "seed": seed, "rate_rps": rates[0] if k == 0 else todo[0],
                "requests": len(sample),
                "served_tokens": sum(len(r["obj"].output) for r in sample),
                "program": {"served_logit_gap": max(served_gaps(
                    seed, m, padded, max_len, sample))},
                "control": {"served_logit_gap": max(served_gaps(
                    seed, m, padded, max_len, sample, fp8=True))}}))
    return rows


def main(argv=None) -> int:
    import argparse

    from bench.run import _prepare_environment, device_info, load_cell
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    _prepare_environment()
    spec = load_cell(args.workload)
    print(json.dumps({"device": device_info(spec["cell"]["chips"])}),
          flush=True)
    if args.sweep:
        sweep(spec, [float(r) for r in args.sweep.split(",")],
              args.seconds,
              [args.first_seed + i for i in range(args.seeds)])
        return 0
    for i in range(args.seeds):
        t0 = time.perf_counter()
        row = readings(spec, args.first_seed + i, i < args.control_seeds,
                       args.seconds)
        row["wall_s"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
