"""Share of the chip's bf16 peak that the fleet's macro scan reaches:
the scan's arithmetic, counted from the fleet's shapes
(`bench.flops.fleet_scan_flops`), over the device time of the scan
program times the peak. The scan is the program that holds the device
longest in the traced experiment."""
from bench import flops


def read(obs):
    shape = obs.get("fleet_shape")
    modules = obs["trace"].modules_s
    if shape is None or not modules:
        return None
    scan_s = max(modules.values())
    peak = flops.peaks(obs["device_kind"])["bf16_flops"]
    return 100.0 * flops.fleet_scan_flops(shape) / (scan_s * peak)
