"""Closed loop: the cell's ``clients`` each send their next request when
the last one finishes, from the start of the lead-in until the window
closes; the run ends at the close."""


class Clients:
    closed = True

    def __init__(self, traffic, clients: int, window_s: float):
        self.clients = clients
        self.queue = traffic.batch(clients * 64)
        self.window_s = window_s
        self.i = self.live = 0

    def take(self, now: float):
        out = []
        while (now < self.window_s and self.live < self.clients
               and self.i < len(self.queue)):
            out.append((self.queue[self.i], now))
            self.i += 1
            self.live += 1
        return out

    def next_due(self):
        return None

    def finished(self, req, tokens, now: float) -> None:
        self.live -= 1


def source(traffic, workload: dict, lead_s: float, window_s: float):
    return Clients(traffic, int(workload["clients"]), window_s)
