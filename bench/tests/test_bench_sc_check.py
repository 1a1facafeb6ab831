"""The sc-sync output check at a CPU-test size: a sound run is correct;
the control (the reference computed in bfloat16, one step below the
configuration's float32) and each fault the cell can have, planted in
the timed path, are not."""
import time

import jax.numpy as jnp
import pytest

from bench import calibrate, harness
from bench.drivers import sync_rounds

SEED = 3000000029


def _run(root, driver=None):
    args = harness.parse(["--workload", "sc-sync", "--seed", str(SEED),
                          "--seconds", "0.5", "--trace", "0"])
    return harness.run(args, time.perf_counter(), root=root,
                       require_chip=False, peak_kind="TPU v5 lite",
                       driver=driver)


def _faulty_step(monkeypatch, fault):
    from repro.core import runtime
    step = runtime.cohort_step

    def broken(apply_fn, opt, params, state, bx, by, *rest):
        if fault == "half_batch":
            b = bx.shape[1] // 2
            return step(apply_fn, opt, params, state, bx[:, :b], by[:, :b],
                        *rest)
        _, _, loss = step(apply_fn, opt, params, state, bx, by, *rest)
        return params, state, loss                # state left unchanged

    monkeypatch.setattr(runtime, "cohort_step", broken)


def test_sound_run_is_correct(small_root):
    res = _run(small_root)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_in_the_cohort_step_is_caught(small_root, monkeypatch, fault):
    _faulty_step(monkeypatch, fault)
    res = _run(small_root)
    assert not res["correct"], res["checks"]


def test_altered_targets_are_caught(small_root, monkeypatch):
    from repro.core.runtime import ServerBus
    fire = ServerBus.fire

    def altered(self, t):
        fire(self, t)
        self.fed.targets = self.fed.targets[..., ::-1]   # classes swapped

    monkeypatch.setattr(ServerBus, "fire", altered)
    res = _run(small_root)
    assert not res["correct"], res["checks"]


def test_control_in_bfloat16_is_not_correct(small_root):
    cell = harness.load_cell("sc-sync", small_root)
    got = calibrate.training_reading(cell, SEED, jnp.bfloat16, 1.0)
    assert any(got[k] > cell.limits[k] for k in got), got
    base = sync_rounds.Driver(cell.config, cell.traffic, SEED)
    assert base.check_rounds == 3
