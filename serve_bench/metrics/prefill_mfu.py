"""Whole prefill chunk's share of its roofline, in %: the least time of
the window's prefill chunks (cost.prefill_chunk, computed tokens only)
over the device time of the programs that run the paged prefill kernel."""


def read(rec):
    dev = (rec["trace"] or {}).get("modules_s", {}).get("prefill", 0.0)
    return 100.0 * rec["prefill_least_s"] / dev if dev else None
