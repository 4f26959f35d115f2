"""Readings that the limits of `correct` are set from, for one cell.

    python bench/calibrate.py --workload <cell> --seeds 12 [--control-seeds 3]
        [--seconds 20] [--fault stale_state]

For each seed, in one process: the numbers a sound run of the program
compares (its lower readings), and on the first `--control-seeds`
seeds the same numbers for the control, the reference computed one
precision below the configuration's (its upper readings):

* fleet cells: the float64 reference with every stored quantity
  rounded to bfloat16, against the float64 reference;
* serving cells: at each served position, the token that the reference
  with float8 (e4m3) matrices ranks first, read in the float32
  reference, over the same requests as the program's check.

`--fault` reads, instead of the control, the program with one fault
planted under the timed path (see bench/tests/test_faults.py). Each
reading is one JSON line on standard output. It needs the chip.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def fleet_readings(spec: dict, seed: int, control: bool) -> dict:
    from bench import generate
    from bench.drivers import fleet
    from bench.reference import fleet as ref
    out = fleet.run(spec, seed=seed, seconds=0.0, trace=False)
    row = {"seed": seed, "correct": out["correct"],
           "program": {c["name"]: c["value"] for c in out["checks"]}}
    if control:
        config, traffic = spec["config"], spec["traffic"]
        exp_seed = fleet.experiment_seed(seed, 0)
        trace = generate.sequential(traffic, config["duration_s"], exp_seed)
        rate = traffic["arrival"]["rate_rps"]
        exact = ref.simulate(config, rate, trace, exp_seed)
        low = ref.simulate(config, rate, trace, exp_seed,
                           store=ref.bf16_store)
        row["control"] = {f"{k}_rel": fleet.rel(low[k], exact[k])
                          for k in fleet.COMPARED}
    return row


def serve_readings(spec: dict, seed: int, control: bool,
                   seconds: float) -> dict:
    from bench.drivers import serve
    config, traffic = spec["config"], spec["traffic"]
    s = config["serving"]
    preroll, reqs = serve.schedule(traffic, seconds, seed,
                                   config["model"]["vocab_size"])
    server = serve.Server(config, seed, [r["input_tokens"] for r in reqs])
    origin, live = serve.play_preroll(server.engine, preroll)
    serve.open_loop(server.engine, reqs, origin, seconds, live)
    serve.drain(server.engine, live, origin, serve.DRAIN_LIMIT_S)
    padded = server.cfg.padded_vocab
    server.free()
    sample = serve.sample_for_reference(reqs, seed, traffic["check_tokens"],
                                        traffic["check_requests"])
    gaps = serve.served_gaps(seed, config["model"], padded, s["max_len"],
                             sample)
    row = {"seed": seed, "requests": len(sample),
           "served_tokens": sum(len(r["obj"].output) for r in sample),
           "program": {"served_logit_gap": max(gaps)}}
    if control:
        fp8 = serve.served_gaps(seed, config["model"], padded, s["max_len"],
                                sample, fp8=True)
        row["control"] = {"served_logit_gap": max(fp8)}
    return row


@contextlib.contextmanager
def _stale_state():
    """The step returns its state unchanged: the fleet's macro scan is
    skipped; the serving decode step hands back the cache it was given."""
    from repro.serving.engine import InferenceEngine
    from repro.sim.fleetsim import FleetEngine
    real_decode, real_run = InferenceEngine._decode_fn, FleetEngine._run_jax

    def stale(self, params, cache, tokens, active):
        return real_decode(self, params, cache, tokens, active)[0], cache
    InferenceEngine._decode_fn = stale
    FleetEngine._run_jax = lambda self, arr, start: None
    try:
        yield
    finally:
        InferenceEngine._decode_fn = real_decode
        FleetEngine._run_jax = real_run


@contextlib.contextmanager
def _altered_answer():
    """An answer altered where it is produced: each served token is
    shifted by one; each aging settlement of the fleet is scaled 1.01x."""
    from repro.kernels.aging_update import ops
    from repro.serving.engine import InferenceEngine
    real_select, real_advance = InferenceEngine._select_token, ops.advance_fleet
    InferenceEngine._select_token = (
        lambda self, row: (real_select(self, row) + 1)
        % self.model.cfg.vocab_size)
    ops.advance_fleet = lambda *a, **k: real_advance(*a, **k) * 1.01
    try:
        yield
    finally:
        InferenceEngine._select_token = real_select
        ops.advance_fleet = real_advance


FAULTS = {"stale_state": _stale_state, "altered_answer": _altered_answer}


def main(argv=None) -> int:
    from bench.run import _prepare_environment, device_info, load_cell
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args(argv)
    _prepare_environment()
    spec = load_cell(args.workload)
    print(json.dumps({"device": device_info(spec["cell"]["chips"])}),
          flush=True)
    driver = spec["config"]["driver"]
    planted = FAULTS[args.fault]() if args.fault else contextlib.nullcontext()
    with planted:
        for i in range(args.seeds):
            seed = args.first_seed + i
            control = i < args.control_seeds and not args.fault
            t0 = time.perf_counter()
            if driver == "fleet":
                row = fleet_readings(spec, seed, control)
            else:
                row = serve_readings(spec, seed, control, args.seconds)
            row["fault"] = args.fault
            row["wall_s"] = time.perf_counter() - t0
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
