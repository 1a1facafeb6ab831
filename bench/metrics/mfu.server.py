"""The whole fire's share of the chip's bf16 peak: the FLOPs of the fires
in the traced window (``bench/flops.py``: the two Eq. 2 strips and Eq. 5
per fire) over the window's time."""


def read(red, counters, peak):
    if not counters.get("fires"):
        return None
    work = counters["fire_flops"] * counters["fires"]
    return 100.0 * work / (red.window_s * red.chips
                           * peak["bf16_flops_per_s"])
