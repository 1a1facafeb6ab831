"""End-to-end determinism: fresh-process federate runs must agree.

Two subprocess invocations of the federate CLI with the same seed — one
on a single device, one on the forced 8-device host mesh — must land on
identical summaries. This is the user-facing version of the sharding
parity tests: it catches seed plumbing that only diverges across
process boundaries (env-dependent key derivation, device-count-dependent
batch draws — the PR 5 bug class) that in-process tests can't see.

Marked slow: two cold jax processes. CI runs it in the analysis lane.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

ARGS = ["--rounds", "2", "--batch", "4", "--eval-every", "1",
        "--samples-per-client", "12", "--ref-size", "12", "--seed", "0"]

# wall_s is timing; devices/schedule describe the config, not the result
_COMPARED = ("final_acc", "selected_acc", "macro_precision",
             "macro_recall", "bytes_up", "bytes_down", "server_rounds",
             "rounds", "uplink", "downlink")


def _run_federate(devices: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["REPRO_KERNEL_BACKEND"] = "jnp"
    if devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{devices}").strip()
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.federate",
         *ARGS, "--devices", str(devices)],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    # config lines first, then the indented summary JSON to EOF
    lines = proc.stdout.splitlines()
    start = next(i for i, ln in enumerate(lines) if ln.strip() == "{")
    return json.loads("\n".join(lines[start:]))


@pytest.mark.slow
def test_federate_deterministic_across_device_counts():
    one = _run_federate(1)
    eight = _run_federate(8)
    for k in _COMPARED:
        assert k in one, f"summary key {k} missing: {sorted(one)}"
        a, b = one[k], eight[k]
        if isinstance(a, float):
            # XLA per-shard reduction tiling admits ULP-level drift (same
            # tolerance as the in-process sharding parity tests)
            assert a == pytest.approx(b, rel=0, abs=1e-6), (k, a, b)
        else:
            assert a == b, (k, a, b)
