"""Device milliseconds per sync round in the ``nemotron-h`` family's
Mamba-2 layers: chip-0 time in ops whose HLO op_name carries the
``nemotron_h.mamba`` scope (projections, conv, the chunked SSD, gated
norm; the cohort step's forward, rematerialised forward and backward,
and the messengers' forward), from the trace (``bench/scopes.py``)."""
from bench import scopes

SCOPE = "nemotron_h.mamba"


def read(red, counters, peak):
    if not counters.get("rounds"):
        return None
    seconds = scopes.device_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / counters["rounds"]
