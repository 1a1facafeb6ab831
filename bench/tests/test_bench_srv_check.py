"""The srv16k-delta output check at a CPU-test size: a sound run is
correct; the control (the program's own dense16 wire, one step below the
configuration's float32) and each fault the cell can have, planted in
the timed path, are not."""
import time

import numpy as np
import pytest

from bench import calibrate, harness
from bench.drivers import server_delta

SEED = 3000000031


class Faulty(server_delta.Driver):
    fault = None

    def setup(self):
        super().setup()
        bus = self.bus
        deliver, fire = bus.deliver, bus.fire
        if self.fault == "state_unchanged":
            def stale(t):
                bus.n_triggers += 1
                bus.uploads_since_fire = 0
                bus.fresh_since_fire[:] = False
            bus.fire = stale
        elif self.fault == "half_batch":
            def half(t, msg, mask, **kw):
                mask = mask.copy()
                mask[np.flatnonzero(mask)[::2]] = False
                return deliver(t, msg, mask, **kw)
            bus.deliver = half
        elif self.fault == "answer_altered":
            def altered(t):
                fire(t)
                fed = bus.fed
                fed.targets = fed.targets.at[0].set(fed.targets[0, :, ::-1])
            bus.fire = altered


def _run(root, config_update=None, fault=None):
    cell = harness.load_cell("srv16k-delta", root)
    drv = Faulty(dict(cell.config, **(config_update or {})), cell.traffic,
                 SEED)
    drv.fault = fault
    args = harness.parse(["--workload", "srv16k-delta", "--seed", str(SEED),
                          "--seconds", "0.3", "--trace", "0"])
    return harness.run(args, time.perf_counter(), root=root,
                       require_chip=False, peak_kind="TPU v5 lite",
                       driver=drv)


def test_sound_run_is_correct(small_root):
    res = _run(small_root)
    assert res["correct"], res["checks"]
    assert res["metrics"]["fire_ms"]["value"] > 0
    assert {"div", "nbrs"} <= set(res["checks"])


def test_stale_divergence_cache_is_caught(small_root):
    """The delta update leaves the (N,N) cache as it was: the repository,
    grades, pool and targets over the chosen neighbours stay sound, so
    only the cache and the neighbour choice can catch it."""
    cell = harness.load_cell("srv16k-delta", small_root)
    drv = calibrate.StaleCache(cell.config, cell.traffic, SEED)
    args = harness.parse(["--workload", "srv16k-delta", "--seed", str(SEED),
                          "--seconds", "0.3", "--trace", "0"])
    res = harness.run(args, time.perf_counter(), root=small_root,
                      require_chip=False, peak_kind="TPU v5 lite",
                      driver=drv)
    checks = res["checks"]
    assert not res["correct"], checks
    for name in ("div", "nbrs"):
        assert checks[name]["value"] > checks[name]["limit"], checks


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_is_caught(small_root, fault):
    res = _run(small_root, fault=fault)
    assert not res["correct"], res["checks"]


def test_control_on_the_dense16_wire_is_not_correct(small_root):
    res = _run(small_root, {"uplink": "dense16", "downlink": "dense16"})
    assert not res["correct"], res["checks"]
