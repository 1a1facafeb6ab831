import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# sizes a CPU test run holds; every width stays the configuration's
SMALL = {"sc": {"samples_per_client": 40, "ref_size": 24},
         "srv16k": {"n_clients": 1024}}


def copy_benchmark(dst: str, small: bool = True) -> str:
    """``BENCHMARK.json`` and ``bench/`` copied to ``dst``, with each
    configuration cut to a CPU-test size."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    if small:
        for name, cut in SMALL.items():
            path = os.path.join(dst, "bench", "configs", name + ".json")
            with open(path) as f:
                cfg = json.load(f)
            cfg.update(cut)
            with open(path, "w") as f:
                json.dump(cfg, f)
    return dst


@pytest.fixture
def small_root(tmp_path):
    return copy_benchmark(str(tmp_path))
