"""A configuration, a traffic mix, a cell and a per-layer metric added as
new files (and entries in BENCHMARK.json) are found by name, with no
existing file of the harness edited."""
import json
import os
import subprocess
import sys

from bench import harness
from bench.tests.conftest import REPO, copy_benchmark


def _write(path, obj):
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


def test_new_files_are_found_by_name(tmp_path):
    root = copy_benchmark(str(tmp_path), small=False)
    bench = os.path.join(root, "bench")
    cfg = json.load(open(os.path.join(bench, "configs", "srv16k.json")))
    _write(os.path.join(bench, "configs", "srv4k.json"),
           dict(cfg, name="srv4k", n_clients=4096))
    _write(os.path.join(bench, "traffic", "delta-u64.json"),
           {"driver": "server_delta", "uploads_per_round": 64, "pool": 2,
            "warm_rounds": 2, "check_block": 512})
    _write(os.path.join(bench, "limits", "srv4k-delta.json"),
           {"grade": 1e-4, "pool": 0, "repo": 0,
            "targets": 1e-2})
    _write(os.path.join(bench, "metrics", "strips_per_fire.py"),
           "def read(red, counters, peak):\n"
           "    return red.module_time('jit__pair_call')[1] / "
           "counters['fires']\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "srv4k", "source": "x",
                            "file": "bench/configs/srv4k.json",
                            "reduced": ["n_clients"], "why": "x"})
    spec["workloads"].append({"name": "srv4k-delta", "config": "srv4k",
                              "traffic": "delta-u64", "chips": 1,
                              "why": "x"})
    for m in spec["end_to_end"]:
        if m["name"] == "fire_ms":
            m["workloads"].append("srv4k-delta")
    spec["per_layer"].append({"name": "strips_per_fire", "unit": "1",
                              "better": "lower",
                              "source": "device_trace", "layer": "kernels",
                              "moves": "fire_ms",
                              "workloads": ["srv4k-delta"]})
    _write(os.path.join(root, "BENCHMARK.json"), spec)

    cell = harness.load_cell("srv4k-delta", root)
    assert cell.config["n_clients"] == 4096 and cell.chips == 1
    assert cell.traffic["uploads_per_round"] == 64
    assert cell.limits["pool"] == 0
    assert [m["name"] for m in cell.end_to_end] == ["fire_ms", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["strips_per_fire"]
    assert harness.load_driver(cell).__module__ == \
        "bench.drivers.server_delta"

    class Red:
        def module_time(self, name):
            return (0.5, 20) if name == "jit__pair_call" else (0.0, 0)

    read = harness.load_reader("strips_per_fire", root)
    assert read(Red(), {"fires": 10}, {}) == 2.0
    # the cells already there are unchanged
    old = harness.load_cell("srv16k-delta", root)
    assert "strips_per_fire" not in [m["name"] for m in old.per_layer]


def test_every_cell_metric_and_file_of_the_benchmark_resolves():
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert harness.load_driver(cell)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.load_reader(m["name"]))
    assert harness.peak_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_run_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench", "run.py"),
         "--workload", "sc-sync", "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr
