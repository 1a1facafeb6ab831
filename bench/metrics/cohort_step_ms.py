"""Device milliseconds per round spent in the client cohort-step programs
(the jitted ``_cohort_step`` of ``repro.core.client``), from the trace."""


def read(red, counters, peak):
    seconds, calls = red.module_time("jit__cohort_step")
    if not calls or not counters.get("rounds"):
        return None
    return 1e3 * seconds / counters["rounds"]
