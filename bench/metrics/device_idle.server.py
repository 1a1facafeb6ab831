"""Share of the traced window of server fires in which no operation ran
on the device (trace: the union of XLA op intervals)."""


def read(red, counters, peak):
    if not counters.get("fires"):
        return None
    return 100.0 * red.idle_share
