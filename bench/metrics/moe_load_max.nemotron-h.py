"""How unevenly the ``nemotron-h`` family's held experts are loaded in the
window: in each expert layer, the busiest held expert's token choices
over the mean of the layer's held experts, the largest over the layers
(1 is even). From the program's ``expert_load.nemotron-h`` counter, read
once after the window."""


def read(red, counters, peak):
    load = counters.get("expert_load")
    if not load:
        return None
    ratios = [max(layer) / (sum(layer) / len(layer))
              for layer in load if sum(layer) > 0]
    return max(ratios) if ratios else None
