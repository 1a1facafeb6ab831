"""Device-busy milliseconds per server fire (trace: the union of XLA op
intervals in the window, over the fires it holds)."""


def read(red, counters, peak):
    if not counters.get("fires"):
        return None
    return 1e3 * red.busy_s / counters["fires"]
