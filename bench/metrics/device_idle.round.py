"""Share of the traced window of sync rounds in which no operation ran on
the device (trace: the union of XLA op intervals)."""


def read(red, counters, peak):
    if not counters.get("rounds"):
        return None
    return 100.0 * red.idle_share
