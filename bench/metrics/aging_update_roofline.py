"""Share of its roofline that the aging-update Pallas kernel reaches:
the least time its bytes need at the chip's HBM bandwidth (four float32
inputs and one output over the padded lanes, two calls per idling
period; the arithmetic is far below the compute bound), over the device
time of the kernel's events in the traced experiment."""
from bench import flops

KERNEL = "aging_update"


def read(obs):
    shape = obs.get("fleet_shape")
    if shape is None:
        return None
    kernel_s = sum(v for k, v in obs["trace"].ops_s.items() if KERNEL in k)
    if kernel_s <= 0:
        return None
    p = flops.peaks(obs["device_kind"])
    per_call, calls = flops.aging_update_bytes(shape)
    least = calls * max(per_call / p["hbm_bytes_per_s"],
                        flops.aging_update_flops(shape) / p["bf16_flops"])
    return 100.0 * least / kernel_s
