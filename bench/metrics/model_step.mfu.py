"""Share of the chip's bf16 peak that the serving engine's model
programs reach: the model operations of every prefill and decode step in
the traced window (`bench.flops`, from the prompt lengths and the active
slots' contexts) over the device time of the prefill and decode programs
times the peak."""
from bench import flops

PREFILL, DECODE = "prefill", "decode"


def read(obs):
    m = obs.get("model")
    if m is None:
        return None
    summary = obs["trace"]
    device_s = summary.module_s(PREFILL) + summary.module_s(DECODE)
    if device_s <= 0:
        return None
    work = (sum(flops.prefill_flops(m, s) for s in obs["prefill_tokens"])
            + sum(flops.decode_flops(m, c) for c in obs["decode_contexts"]))
    peak = flops.peaks(obs["device_kind"])["bf16_flops"]
    return 100.0 * work / (device_s * peak)
