"""Homogeneous Poisson arrivals at `rate_rps`."""


def next_gap_fn(params: dict):
    mean_gap = 1.0 / float(params["rate_rps"])

    def next_gap(rng, t: float) -> float:
        return rng.exponential(mean_gap)
    return next_gap


def quantile_gap(params: dict, u: float) -> float:
    """The inter-arrival gap at quantile `u` of its distribution."""
    import math
    return -math.log1p(-u) / float(params["rate_rps"])
