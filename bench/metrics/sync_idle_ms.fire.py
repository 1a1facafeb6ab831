"""Device-idle milliseconds per server fire in gaps that start while the host
waits in a ``repro.host_sync`` read: the device drained its queue because
the host was waiting on it (trace: ``bench/spans.py``)."""
from bench import spans


def read(red, counters, peak):
    sp = spans.latest()
    if sp is None or not counters.get("fires"):
        return None
    return 1e3 * sp.sync_idle_s() / counters["fires"]
