"""Device milliseconds of the prefill programs per 1,000 prompt tokens
prefilled in the traced serving window."""
PREFILL = "prefill"


def read(obs):
    if obs.get("model") is None or not obs["prefill_tokens"]:
        return None
    device_s = obs["trace"].module_s(PREFILL)
    if device_s <= 0:
        return None
    return 1e3 * device_s / (sum(obs["prefill_tokens"]) / 1e3)
