"""Mean time of one ``ServeEngine.step()`` call in the window, in ms, on
the harness's clock around the call."""


def read(rec):
    t = rec["ticks"]
    return 1e3 * sum(x.t1 - x.t0 for x in t) / len(t) if t else None
