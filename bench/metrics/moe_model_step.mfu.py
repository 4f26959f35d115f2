"""Share of the chip's bf16 peak that the MoE model's serving programs
reach: this chip's model operations of every prefill and decode step in
the traced window (`bench.flops_moe`: attention, the dense layer, the
router and shared experts from the prompt lengths and the active slots'
contexts; the held routed experts from the engine's routing counts)
over the device time of the prefill and decode programs times the
peak."""
from bench import flops, flops_moe

PREFILL, DECODE = "prefill", "decode"


def read(obs):
    moe = obs.get("moe")
    if moe is None:
        return None
    summary = obs["trace"]
    device_s = summary.module_s(PREFILL) + summary.module_s(DECODE)
    if device_s <= 0:
        return None
    m = obs["model"]
    work = (sum(flops_moe.prefill_flops(m, s) for s in obs["prefill_tokens"])
            + sum(flops_moe.decode_flops(m, c)
                  for c in obs["decode_contexts"])
            + flops_moe.expert_flops(m, sum(moe["tokens"])))
    peak = flops.peaks(obs["device_kind"])["bf16_flops"]
    return 100.0 * work / (device_s * peak)
