"""99th percentile of the gaps between consecutive output tokens that end
in the traced serving window: the decode steps that also carry an
admission's prefill. Read on the run's own host clock. About 30 of a
window's ~3,000 gaps lie beyond it, and it swings with the few longest
prompts, so it is no end-to-end metric."""


def read(obs):
    return obs.get("latency", {}).get("serve_tbt_p99_ms")
