"""Share of its roofline that the routed-expert grouped matmul reaches:
the least time of its work over the device time of its operations in
the traced serving window. The operations are the Megablox grouped
matmul's Pallas calls, named `gmm` in the device trace. The work comes
from the engine's routing counts of the held experts over the window
(`bench.flops_moe`): per phase, the larger of the bytes at the chip's
HBM bandwidth (each non-empty group's three bf16 matrices once, each
routed token's activation in and out) and the operations at its bf16
peak; prefill is bound by the operations, a decode step by the bytes."""
from bench import flops, flops_moe

KERNEL = "gmm"


def read(obs):
    moe = obs.get("moe")
    if moe is None:
        return None
    kernel_s = sum(v for k, v in obs["trace"].ops_s.items() if KERNEL in k)
    if kernel_s <= 0:
        return None
    m, p = obs["model"], flops.peaks(obs["device_kind"])
    least = sum(max(flops_moe.expert_bytes(m, tokens, groups)
                    / p["hbm_bytes_per_s"],
                    flops_moe.expert_flops(m, tokens) / p["bf16_flops"])
                for tokens, groups in zip(moe["tokens"], moe["groups"]))
    return 100.0 * least / kernel_s
