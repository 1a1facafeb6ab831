"""The whole round's share of the chip's bf16 peak: the FLOPs of the
rounds in the traced window (``bench/flops.py``: every client's forward
and backward over its batch and the reference set, its messenger, the
server's Eq. 2 and Eq. 5) over the window's time."""


def read(red, counters, peak):
    if not counters.get("rounds"):
        return None
    work = counters["round_flops"] * counters["rounds"]
    return 100.0 * work / (red.window_s * red.chips
                           * peak["bf16_flops_per_s"])
