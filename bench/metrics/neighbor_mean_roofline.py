"""Roofline share of the Eq. 5 kernel (the jitted ``neighbor_mean`` of
``repro.kernels``): the least time the chip needs for the work Eq. 5
needs with K neighbours per client (``bench/flops.py``, bound by bytes
at these shapes), over the kernel programs' device time."""
from bench import flops


def read(red, counters, peak):
    seconds, calls = red.module_time("jit_neighbor_mean")
    if not calls or "nm_work" not in counters:
        return None
    least, _ = flops.roofline_s(*counters["nm_work"], peak)
    return 100.0 * calls * least / seconds
