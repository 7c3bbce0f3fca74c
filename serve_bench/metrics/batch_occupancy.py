"""Mean share of the engine's slots holding a request per tick, in %:
window differences of ``occupancy_sum`` over ``ticks``."""


def read(rec):
    s = rec["stats"]
    return 100.0 * s["occupancy_sum"] / s["ticks"] if s.get("ticks") else None
