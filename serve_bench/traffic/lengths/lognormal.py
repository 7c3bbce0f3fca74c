"""Lognormal lengths: ``median`` and ``sigma`` (of the log)."""
from statistics import NormalDist

import numpy as np


def inverse_cdf(dist: dict, u: np.ndarray) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
    return dist["median"] * np.exp(dist["sigma"] * z)
