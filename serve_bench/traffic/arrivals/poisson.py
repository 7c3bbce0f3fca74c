"""Open loop, Poisson arrivals at the cell's ``rate_per_s``: exponential
gaps at their mid-quantiles, in an order fixed by their count."""
import numpy as np

from serve_bench import traffic as tr


def exp_gaps(n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps of mean 1, at mid-quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u)


def source(traffic, workload: dict, lead_s: float, window_s: float):
    return tr.Schedule(traffic.open_loop(
        float(workload["rate_per_s"]), lead_s, window_s, exp_gaps,
        int(workload.get("preload", 0))))
