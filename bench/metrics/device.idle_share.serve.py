"""Share of the traced serving window in which no operation ran on the
device: 1 - (union of device op intervals) / traced window."""


def read(obs):
    if "model" not in obs or obs["trace"].n_devices == 0:
        return None
    return 100.0 * obs["trace"].idle_share
