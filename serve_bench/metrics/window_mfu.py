"""Least time of every step in the traced window (decode steps and prefill
chunks, as cost.py counts them) over the window's wall time, in %."""


def read(rec):
    least = rec["decode_least_s"] + rec["prefill_least_s"]
    return 100.0 * least / rec["window_s"] if rec["window_s"] else None
