"""Whole decode step's share of its roofline, in %: the least time of the
window's decode steps (cost.decode_step: the larger of FLOPs over the peak
and required bytes over the bandwidth) over the device time of the
programs that run the paged decode kernel."""


def read(rec):
    dev = (rec["trace"] or {}).get("modules_s", {}).get("decode", 0.0)
    return 100.0 * rec["decode_least_s"] / dev if dev else None
