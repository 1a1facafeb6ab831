"""Roofline share of the Eq. 2 strip kernel (the jitted ``_pair_call`` of
``repro.kernels.pairwise_kl``): the least time the chip needs for each
(u,N) or (N,u) strip's work (``bench/flops.py``, bound by bytes at these
shapes), over the strip programs' device time."""
from bench import flops


def read(red, counters, peak):
    seconds, calls = red.module_time("jit__pair_call")
    if not calls or "strip_work" not in counters:
        return None
    least, _ = flops.roofline_s(*counters["strip_work"], peak)
    return 100.0 * calls * least / seconds
