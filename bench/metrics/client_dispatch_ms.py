"""Host milliseconds per sync round inside the program's
``repro.local_round`` span: drawing each cohort's batch (RNG split,
sampling, gathers) and dispatching its step (trace: ``bench/spans.py``)."""
from bench import spans


def read(red, counters, peak):
    sp = spans.latest()
    if sp is None or not counters.get("rounds"):
        return None
    return 1e3 * sp.host_s("repro.local_round") / counters["rounds"]
