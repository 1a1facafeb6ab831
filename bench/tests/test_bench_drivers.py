"""Each driver's set-up plus two steps at a CPU-test size, called directly,
and its output check against the cell's limits."""
import numpy as np

from bench import harness
from bench.drivers import server_delta, sync_rounds


def test_sync_rounds_setup_and_two_rounds(small_root):
    cell = harness.load_cell("sc-sync", small_root)
    drv = sync_rounds.Driver(cell.config, cell.traffic, 3000000021)
    drv.setup()
    assert drv.round == drv.check_rounds == 3
    assert len(drv.loss) == 3 * len(cell.config["families"])
    out = drv.window(0.0)
    drv._round()
    drv._block()
    assert drv.round == 5 and out["attempted"] == 1 and out["failed"] == 0
    assert out["e2e"]["round_ms"] > 0 and out["counters"]["round_flops"] > 0
    drv.release()
    checks = dict(drv.check())
    assert set(checks) == set(cell.limits)
    assert all(v <= cell.limits[k] for k, v in checks.items()), checks


def test_server_delta_setup_and_two_fires(small_root):
    cell = harness.load_cell("srv16k-delta", small_root)
    drv = server_delta.Driver(cell.config, cell.traffic, 3000000023)
    drv.setup()
    warm = cell.traffic["warm_rounds"]
    assert drv.round == warm and drv.bus.n_triggers == warm + 1
    out = drv.window(0.0)
    drv._round()
    drv._block()
    assert drv.round == warm + 2 and out["attempted"] == 1
    assert out["failed"] == 0 and out["e2e"]["fire_ms"] > 0
    # each round uploads distinct clients
    sent = np.flatnonzero(drv.last == warm + 1)
    assert len(sent) == cell.traffic["uploads_per_round"]
    drv.release()
    checks = dict(drv.check())
    assert set(checks) == set(cell.limits)
    assert all(v <= cell.limits[k] for k, v in checks.items()), checks
