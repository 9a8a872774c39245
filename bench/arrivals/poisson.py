"""Poisson arrivals: independent exponential gaps at the mean rate."""


def process(mix, rate: float):
    return lambda rng, k: rng.exponential(1.0 / rate, k)
