"""90th percentile of the time to first token over the requests due in
the traced serving window: admissions that wait for a slot or queue
behind other prefills. Read on the run's own host clock. A window holds
18 requests, so about 2 lie beyond it: too few for an end-to-end
metric."""


def read(obs):
    return obs.get("latency", {}).get("serve_ttft_p90_ms")
