"""Mean time a request waited between ``submit`` and its first admission
into a slot, in ms: window differences of the engine's ``queue_wait_s``
over ``admissions`` (a restore after preemption is not an admission).
None where the engine keeps no such counter."""


def read(rec):
    s = rec["stats"]
    n = s.get("admissions")
    return 1e3 * s["queue_wait_s"] / n if n else None
