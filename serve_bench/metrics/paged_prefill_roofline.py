"""Paged prefill attention kernel's share of its roofline, in %: the least
time of every call (cost.paged_prefill_call, the larger of its two bounds)
over the device time of ``_paged_prefill_kernel`` events."""


def read(rec):
    dev = (rec["trace"] or {}).get("kernels_s", {}).get("paged_prefill", 0.0)
    return 100.0 * rec["paged_prefill_least_s"] / dev if dev else None
