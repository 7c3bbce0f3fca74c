"""Paged decode attention kernel's share of its roofline, in %: the least
time of every call (cost.paged_decode_call over the live lengths the
harness saw) over the device time of ``_paged_kernel`` events."""


def read(rec):
    dev = (rec["trace"] or {}).get("kernels_s", {}).get("paged_decode", 0.0)
    return 100.0 * rec["paged_decode_least_s"] / dev if dev else None
