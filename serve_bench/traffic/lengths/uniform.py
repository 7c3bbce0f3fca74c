"""Uniform lengths over ``[min, max]``."""
import numpy as np


def inverse_cdf(dist: dict, u: np.ndarray) -> np.ndarray:
    return dist["min"] + (dist["max"] - dist["min"]) * np.asarray(u)
