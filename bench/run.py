"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json at the root of the
checkout. Its configuration (`bench/configs/<config>.json`) names the
module that runs it (`bench/drivers/<name>.py`); its traffic mix is
`bench/traffic/<traffic>.json`. With `--trace 0` the result carries the
cell's end-to-end metrics; with `--trace 1` a profiled run carries its
per-layer metrics, each read by `bench/metrics/<metric>.py`, and a
breakdown of the device trace.

The run needs an accelerator: with none, or with fewer chips than the
cell asks for, it exits non-zero and prints no result. The last line of
standard output is one JSON object; the lines before it, and the last
lines of standard error, list each number compared for `correct` beside
its limit.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _prepare_environment() -> None:
    """Paths and the persistent compilation cache, before JAX loads.

    The cache lives where JAX_COMPILATION_CACHE_DIR says, else at the
    fixed path `<checkout>/.jax_cache` (the program's own default too).
    Every compile is cached, however short, so that only a checkout's
    first run of a cell compiles."""
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def load_cell(name: str) -> dict:
    """The cell's BENCHMARK.json entry, its configuration and traffic
    files, and the per-layer metrics that read it."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer}


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] == "cpu":
        raise SystemExit("bench: JAX found no accelerator; refusing to "
                         "measure the host CPU")
    if info["count"] < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{info['count']}")
    return info


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def format_checks(checks: list[dict]) -> str:
    """`name=value (limit L)` for every number compared."""
    return "; ".join(f"{c['name']}={c['value']!r} (limit {c['limit']!r})"
                     for c in checks)


def load_reader(metric: str):
    """The reader of a per-layer metric: `bench/metrics/<metric>.py`,
    whose `read(observations)` returns the value, or None where the
    run gave it nothing to read."""
    import importlib.util
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """Run the cell once; return the result object (not yet printed).
    Set-up is counted from `t_start`."""
    import importlib

    spec = load_cell(name)
    cell, config = spec["cell"], spec["config"]
    device = device_info(cell["chips"])
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    driver = importlib.import_module(f"bench.drivers.{config['driver']}")
    out = driver.run(spec, seed=seed, seconds=seconds, trace=trace,
                     t_start=t_start)
    device["memory_peak_bytes"] = out.pop("memory_peak_bytes")
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}
    if trace:
        from bench import trace as trace_mod
        obs = out["observations"]
        obs["device_kind"] = device["kind"]
        device["busy_s"] = obs["trace"].busy_s
        device["window_s"] = obs["trace"].window_s
        for m in spec["per_layer"]:
            value = load_reader(m["name"]).read(obs)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = trace_mod.breakdown(obs["trace"])
    else:
        for m in spec["end_to_end"]:
            if m["name"] in out["metrics"]:
                result["metrics"][m["name"]] = {"value": out["metrics"][m["name"]],
                                                "unit": m["unit"]}
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in out["checks"]}
    result["_checks_text"] = format_checks(out["checks"])
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _prepare_environment()
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START)
    text = result.pop("_checks_text")
    print(f"run: {time.perf_counter() - T_START:.3f} s in all", file=sys.stderr)
    print(f"checks: {text}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
