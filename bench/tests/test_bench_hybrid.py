"""The ``sc-long`` cell at a CPU-test size: it is found with its metrics;
its driver's output check passes a sound run and fails the half-batch
and state-unchanged faults planted in the timed path; the benchmark's
reference of the hybrid agrees with the program's.

The configuration keeps its structure (32 clients, the hybrid pattern
MEMEM*E, 120 tokens of 25 samples, one SSD chunk) at tiny widths, and
the program's registered ``nemotron-h`` is given the same widths."""
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops_hybrid, harness, ref_nemotron_h
from bench.tests.conftest import REPO, copy_benchmark

SEED = 3000000041
TINY = {"hidden_size": 32, "mamba_num_heads": 4, "mamba_head_dim": 8,
        "n_groups": 2, "ssm_state_size": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8, "router_experts": 8,
        "n_routed_experts": 2, "num_experts_per_tok": 3,
        "moe_intermediate_size": 16,
        "moe_shared_expert_intermediate_size": 24,
        "samples_per_client": 40, "ref_size": 24}


def _model(cfg: dict):
    """The program's ModelConfig for a configuration's numbers."""
    from repro.models.zoo import NEMOTRON_H, hybrid_pattern
    return dataclasses.replace(
        NEMOTRON_H, n_layers=cfg["num_hidden_layers"],
        layer_pattern=hybrid_pattern(
            cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]),
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"],
        shared_d_ff=cfg["moe_shared_expert_intermediate_size"],
        n_experts=cfg["router_experts"],
        experts_held=cfg["n_routed_experts"],
        moe_top_k=cfg["num_experts_per_tok"],
        routed_scale=cfg["routed_scaling_factor"],
        ssm_state=cfg["ssm_state_size"], ssm_heads=cfg["mamba_num_heads"],
        ssm_head_dim=cfg["mamba_head_dim"], ssm_groups=cfg["n_groups"],
        conv_width=cfg["conv_kernel"], ssm_chunk=cfg["chunk_size"],
        norm_eps=cfg["layer_norm_epsilon"])


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    root = copy_benchmark(str(tmp_path))
    path = os.path.join(root, "bench", "configs", "sc-nemotron3-nano.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    with open(path, "w") as f:
        json.dump(cfg, f)
    from repro.models import zoo
    monkeypatch.setattr(zoo, "NEMOTRON_H", _model(cfg))
    return root


def _run(root, driver=None):
    args = harness.parse(["--workload", "sc-long", "--seed", str(SEED),
                          "--seconds", "0.5", "--trace", "0"])
    return harness.run(args, time.perf_counter(), root=root,
                       require_chip=False, peak_kind="TPU v5 lite",
                       driver=driver)


def test_sc_long_is_found_with_its_metrics():
    cell = harness.load_cell("sc-long")
    assert cell.chips == 1 and cell.config["patch"] == 25
    assert harness.load_driver(cell).__module__ == \
        "bench.drivers.sync_rounds_hybrid"
    assert [m["name"] for m in cell.end_to_end] == ["round_ms", "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "device_idle.round", "cohort_step_ms", "mfu.round",
        "cohort_step_ms.nemotron-h", "moe_ms.nemotron-h",
        "mamba_ms.nemotron-h", "moe_load_max.nemotron-h"}
    assert set(cell.limits) == {"loss", "grad", "change"}
    read = harness.load_reader("moe_load_max.nemotron-h")
    assert read(None, {"expert_load": [[2, 2], [1, 3]]}, {}) == 1.5
    assert read(None, {"rounds": 3}, {}) is None
    # the other cells are unchanged
    old = harness.load_cell("sc-sync")
    assert "cohort_step_ms.nemotron-h" not in [m["name"]
                                               for m in old.per_layer]


def test_round_flops_at_published_widths():
    """About 430 MFLOP a token of the hybrid's forward with balanced
    routing, and 52 TFLOP a round for its 256 sequences trained and 240
    forwarded (the other 31 clients and the server add little)."""
    cfg = harness.load_cell("sc-long").config
    # held-expert choices a token, over the three expert layers
    per_token = (3 * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                 / cfg["router_experts"])
    tok = flops_hybrid.hybrid_token(cfg) + per_token * \
        flops_hybrid.routed_choice(cfg)
    assert 4.2e8 < tok < 4.4e8
    total = flops_hybrid.sync_round(cfg, [per_token * 256 * 120])
    assert 5.0e13 < total < 5.6e13


def test_sound_run_is_correct(tiny_root):
    from bench.drivers import sync_rounds_hybrid
    cell = harness.load_cell("sc-long", tiny_root)
    drv = sync_rounds_hybrid.Driver(cell.config, cell.traffic, SEED)
    res = _run(tiny_root, drv)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"loss", "grad", "change"}
    # the messenger's gap is read, and decides nothing
    assert 0 <= drv.msg_gap < 1e-3


def test_routing_comparison_of_one_weight_set(tiny_root):
    """The program's token choices and the reference's, routing the same
    weights in float32 at HIGHEST on the CPU, differ in no choice; the
    reference's choices against another weight set's differ in some."""
    from bench import calibrate_hybrid
    cfg = harness.load_cell("sc-long", tiny_root).config
    w = ref_nemotron_h.init_weights(cfg, jax.random.key(3))
    other = ref_nemotron_h.init_weights(cfg, jax.random.key(4))
    f = ref_nemotron_h.FAMILY
    x = np.asarray(jax.random.normal(jax.random.key(5),
                                     (6, cfg["series_length"])))
    with jax.default_matmul_precision("highest"):
        got = calibrate_hybrid.choice_flips(
            cfg, {"same": (w[f], w[f]), "other": (other[f], w[f])}, x)
    assert got["same"] == [0, 0, 0]
    assert min(got["other"]) > 0
    assert got["choices_per_layer"] == 6 * 120 * cfg["num_experts_per_tok"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_in_the_cohort_step_is_caught(tiny_root, monkeypatch, fault):
    from repro.core import runtime
    for name in ("cohort_step", "expert_cohort_step"):
        step = getattr(runtime, name)

        def broken(apply_fn, opt, params, state, bx, by, *rest, step=step):
            if fault == "half_batch":
                b = bx.shape[1] // 2
                return step(apply_fn, opt, params, state, bx[:, :b],
                            by[:, :b], *rest)
            out = step(apply_fn, opt, jax.tree.map(jnp.copy, params),
                       jax.tree.map(jnp.copy, state), bx, by, *rest)
            return (params, state) + tuple(out[2:])  # state left unchanged

        monkeypatch.setattr(runtime, name, broken)
    res = _run(tiny_root)
    assert not res["correct"], res["checks"]


def test_bench_reference_agrees_with_the_programs(tiny_root):
    """The benchmark's hybrid forward and the program's plain reference,
    on the benchmark's weights, give the same logits."""
    from repro.models import ref_nemotron_h as program_ref
    cfg = harness.load_cell("sc-long", tiny_root).config
    w = ref_nemotron_h.init_weights(cfg, jax.random.key(1))
    p = jax.tree.map(lambda a: a[0], w[ref_nemotron_h.FAMILY])
    x = jax.random.normal(jax.random.key(2), (3, cfg["series_length"]))
    with jax.default_matmul_precision("highest"):
        want = ref_nemotron_h.forward(cfg, p, x)
    got, _ = program_ref.forward(p, _model(cfg), x, cfg["patch"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_the_benchmark_names_what_the_repo_holds():
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    conf = {c["name"]: c for c in spec["configs"]}["sc-nemotron3-nano"]
    cfg = json.load(open(os.path.join(REPO, conf["file"])))
    assert set(conf["reduced"]) == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 7 and cfg["n_routed_experts"] == 8
    assert cfg["published"]["n_routed_experts"] == cfg["router_experts"]


def test_scope_reader_on_a_chip_trace(tmp_path, monkeypatch):
    """On a v5e trace of three ``sc-sync`` rounds, a program without the
    hybrid: the ops carry their HLO op_name, the time under a scope is
    the union of its ops' intervals, and the hybrid's readers find
    nothing and return None."""
    import gzip
    import shutil
    from bench import scopes, spans
    from bench import trace as tr
    fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                           "sc_rounds.xplane.pb.gz")
    path = tmp_path / "run" / "t.xplane.pb"
    path.parent.mkdir()
    with gzip.open(fixture) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    monkeypatch.setattr(spans, "TRACES", str(tmp_path))
    ops = scopes.ops(str(path))
    assert any(n.startswith("jit(_cohort_step)/") for _, _, n in ops)
    step_s = scopes.device_s("jit(_cohort_step)")
    module_s, _ = tr.reduce_trace(str(path)).module_time("jit__cohort_step")
    assert 0.9 * module_s < step_s <= module_s * 1.001
    counters = {"rounds": 3}
    for metric in ("moe_ms.nemotron-h", "mamba_ms.nemotron-h",
                   "cohort_step_ms.nemotron-h", "moe_load_max.nemotron-h"):
        assert harness.load_reader(metric)(None, counters, {}) is None
