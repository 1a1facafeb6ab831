"""90th percentile of the time per server fire, in milliseconds: from one
``repro.deliver`` span's start to the next, the last fire ending at the
window's end (trace: ``bench/spans.py``). With 130-190 fires in a
10-second window, 13 or more lie beyond it."""
from bench import spans


def read(red, counters, peak):
    sp = spans.latest()
    if sp is None or not counters.get("fires"):
        return None
    times = sp.fire_s()
    if not times:
        return None
    return 1e3 * spans.percentile(times, 90)
