"""Device milliseconds per sync round of the ``nemotron-h`` family's cohort
steps: the ``jit__cohort_step`` programs that its ``repro.cohort_step``
spans launched (trace: ``bench/spans.py``)."""
from bench import spans

FAMILY = "nemotron-h"


def read(red, counters, peak):
    sp = spans.latest()
    if sp is None or not counters.get("rounds"):
        return None
    by_family = sp.cohort_step_s()
    if by_family is None or FAMILY not in by_family:
        return None
    return 1e3 * by_family[FAMILY] / counters["rounds"]
