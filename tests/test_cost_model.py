"""Mutation + unit tests for the static cost model (`repro.analysis.cost`).

Mirrors the test_analysis convention: every cost rule is driven on a
seeded-bug variant where it MUST fire and on the real code where it MUST
stay silent, plus unit tests for the interpreter's cost semantics
(fusion, in-place aliasing, scan multipliers, liveness) and the CLI
surfaces that gate CI.
"""
import importlib.util
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.cost import entries, interp, model, rules
from repro.analysis.registry import AnalysisContext, run_rules

REPO_ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def ctx():
    return AnalysisContext()


def _load_script(name: str):
    path = REPO_ROOT / "benchmarks" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# interpreter semantics
# --------------------------------------------------------------------------

def test_dot_flops_exact():
    a = jnp.zeros((8, 32), jnp.float32)
    b = jnp.zeros((32, 16), jnp.float32)
    s = interp.summarize(jax.make_jaxpr(lambda x, y: x @ y)(a, b))
    assert s.flops_by_prim["dot_general"] == 2.0 * 8 * 16 * 32


def test_elementwise_chain_fuses_away():
    # exp -> mul -> single consumer chain: intermediates stay in
    # registers, so the only HBM traffic is one read + one write
    x = jnp.zeros((1024,), jnp.float32)
    s = interp.summarize(
        jax.make_jaxpr(lambda v: jnp.exp(v) * 2.0 + 1.0)(x))
    assert s.temp_bytes == 0.0
    assert s.bytes == pytest.approx(2 * 1024 * 4, rel=0.1)


def test_multi_consumer_intermediate_materializes():
    # p is consumed by BOTH the dot and the rowterm product -> it must
    # hit HBM and count as a live temporary
    x = jnp.zeros((64, 64), jnp.float32)

    def f(v):
        p = jnp.exp(v)
        return p @ v.T + jnp.sum(p * v)

    s = interp.summarize(jax.make_jaxpr(f)(x))
    assert s.temp_bytes >= 64 * 64 * 4


def test_inplace_scatter_aliases_operand():
    # updating 2 rows of a (256,256) cache must not count the cache as a
    # fresh temporary, and traffic is the touched strip, not N^2
    cache = jnp.zeros((256, 256), jnp.float32)
    strip = jnp.ones((2, 256), jnp.float32)

    def f(c, st):
        return c.at[jnp.array([3, 9]), :].set(st)

    s = interp.summarize(jax.make_jaxpr(f)(cache, strip))
    assert s.temp_bytes < 256 * 256 * 4 * 0.1
    assert s.bytes < 256 * 256 * 4


def test_scan_multiplies_flops_by_trip_count():
    x = jnp.zeros((16, 16), jnp.float32)

    def body(c, _):
        return c @ c, None

    def once(v):
        return v @ v

    def looped(v):
        out, _ = jax.lax.scan(body, v, None, length=10)
        return out

    f1 = interp.summarize(jax.make_jaxpr(once)(x)).flops_by_prim
    f10 = interp.summarize(jax.make_jaxpr(looped)(x)).flops_by_prim
    assert f10["dot_general"] == pytest.approx(10 * f1["dot_general"])


def test_broadcast_is_regenerable_but_escaping_broadcast_counts():
    x = jnp.zeros((8, 8), jnp.float32)
    internal = jax.make_jaxpr(
        lambda v: (jnp.broadcast_to(v[0], (8, 8)) + v).sum())(x)
    assert interp.find_blowups(internal, ratio=4.0, floor_bytes=1) == []
    escaping = jax.make_jaxpr(
        lambda v: jnp.broadcast_to(v, (1000,) + v.shape))(x)
    found = interp.find_blowups(escaping, ratio=32.0, floor_bytes=4096)
    assert found and found[0].ratio > 500


def test_fit_exponent_recovers_power_laws():
    xs = (64, 128, 256, 512)
    assert interp.fit_exponent(xs, [4 * x * x for x in xs]) == \
        pytest.approx(2.0, abs=1e-6)
    assert model.leading_exponent(xs, [7 * x for x in xs]) == \
        pytest.approx(1.0, abs=1e-6)
    with pytest.raises(ValueError):
        interp.fit_exponent((64,), (1.0,))


# --------------------------------------------------------------------------
# entries + table
# --------------------------------------------------------------------------

def test_every_entry_traces_and_prices(ctx):
    table = model.cost_table(ctx)
    assert set(table) == set(entries.entry_names())
    for name, s in table.items():
        assert s.flops > 0, name
        assert s.bytes > 0, name
        assert s.peak_bytes >= s.temp_bytes, name


def test_trace_entry_rejects_unknowns():
    with pytest.raises(KeyError, match="unknown cost entry"):
        entries.trace_entry("no-such-entry")
    with pytest.raises(KeyError, match="unknown dims"):
        entries.trace_entry("divergence_matrix", nn=7)


def test_scaling_pins_delta_linear_and_rebuild_quadratic(ctx):
    # THE acceptance invariant: the delta graph path allocates Θ(u·N)
    # temporaries while the full rebuild allocates Θ(N²)
    scaling = model.scaling_report(ctx)
    delta = scaling["sqmd.build_graph_delta"]["temp_bytes"]["leading"]
    full = scaling["divergence_matrix"]["temp_bytes"]["leading"]
    assert delta <= 1.2, f"delta path regressed to Θ(N^{delta:.2f})"
    assert full >= 1.8, f"rebuild should report ≈Θ(N²), got {full:.2f}"
    assert scaling["divergence_matrix"]["flops"]["leading"] == \
        pytest.approx(2.0, abs=0.1)


# --------------------------------------------------------------------------
# mutation suite: each cost rule fires on a seeded bug, silent on real
# --------------------------------------------------------------------------

def _dense_rebuild_delta_scaling():
    """The seeded bug: the delta path 'updated' by a full dense rebuild
    scattered into the cache — the exact regression superlinear-memory
    exists to catch."""
    from repro.core import similarity

    def mutant(cache, repo_logp):
        div = similarity.divergence_matrix(repo_logp)
        return cache.at[:, :].set(div)

    axis_vals = (256, 512, 1024, 2048)
    ys = []
    for n in axis_vals:
        args = (jax.ShapeDtypeStruct((n, n), jnp.float32),
                jax.ShapeDtypeStruct((n, 8, 10), jnp.float32))
        ys.append(interp.summarize(jax.make_jaxpr(mutant)(*args)).temp_bytes)
    rec = {"axis": "n", "values": list(axis_vals),
           "temp_bytes": {"leading": model.leading_exponent(axis_vals, ys),
                          "fit": interp.fit_exponent(axis_vals, ys),
                          "samples": ys}}
    return {"sqmd.build_graph_delta": rec}


def test_superlinear_memory_fires_on_dense_rebuild_mutant(ctx):
    mutant = _dense_rebuild_delta_scaling()
    v = rules.exponent_violations(mutant, {"sqmd.build_graph_delta": 1.2})
    assert len(v) == 1
    assert "Θ(n^" in v[0].message and v[0].rule == "superlinear-memory"
    # and the REAL delta path stays inside the same budget
    real = model.scaling_report(ctx)
    assert rules.exponent_violations(
        real, {"sqmd.build_graph_delta": 1.2}) == []


def test_broadcast_blowup_fires_on_1000x_mutant_silent_on_real(ctx):
    def mutant(w):
        return jnp.broadcast_to(w[:, None], (w.shape[0], 1000))

    j = jax.make_jaxpr(mutant)(jnp.zeros((64,), jnp.float32))
    v = rules.blowup_violations("mutant", j, rules._POLICY_BLOWUP)
    assert v and "broadcast_in_dim" in v[0].message

    budgets = rules.load_budgets()
    for name in entries.entry_names():
        assert rules.blowup_violations(
            name, entries.trace_entry(name), budgets["blowup"]) == [], name


def test_cost_budget_fires_on_regression_and_inflated_budget(ctx):
    table = model.cost_table(ctx)
    budgets = rules.load_budgets()
    assert rules.budget_violations(table, budgets) == []

    # regression: the real cost exceeds a halved budget
    cheap = json.loads(json.dumps(budgets))
    cheap["entries"]["cohort_step"]["flops"] /= 10.0
    v = rules.budget_violations(table, cheap)
    assert any("exceeds budget" in x.message
               and x.where == "cohort_step#flops" for x in v)

    # inflated budget: slack that would hide the next regression
    inflated = json.loads(json.dumps(budgets))
    inflated["entries"]["cohort_step"]["flops"] *= 10.0
    v = rules.budget_violations(table, inflated)
    assert any("stale/inflated" in x.message
               and x.where == "cohort_step#flops" for x in v)


def test_cost_budget_flags_unbudgeted_and_vanished_entries(ctx):
    table = dict(model.cost_table(ctx))
    budgets = json.loads(json.dumps(rules.load_budgets()))
    del budgets["entries"]["serve_step"]
    extinct = table.pop("sqmd.grade")
    del extinct
    v = rules.budget_violations(table, budgets)
    wheres = {x.where for x in v}
    assert "serve_step" in wheres          # traced but unbudgeted
    assert "sqmd.grade" in wheres          # budgeted but no longer traced


def test_kernel_intensity_fires_on_defused_kernel_and_bad_crosscheck():
    # a 'kernel' that streams a big array through one add has intensity
    # ~0.125 flops/byte — below any matmul-kernel floor
    j = jax.make_jaxpr(lambda x: x + 1.0)(
        jnp.zeros((4096,), jnp.float32))
    s = interp.summarize(j)
    v = rules.intensity_violations("mutant", s, floor=1.0)
    assert v and "below the roofline floor" in v[0].message

    # a cost model whose dot FLOPs disagree 100x with the compiled HLO
    ref = rules.kernel_probes()["pairwise_kl"]
    sk = interp.summarize(jax.make_jaxpr(ref[0])(*ref[1]))
    dots = sk.flops_by_prim["dot_general"]
    v = rules.intensity_violations("pairwise_kl", sk, floor=0.0,
                                   hlo_flops=dots * 100, band=3.0)
    assert v and "disagree" in v[0].message
    assert rules.intensity_violations("pairwise_kl", sk, floor=0.0,
                                      hlo_flops=dots * 1.5, band=3.0) == []


def test_kernel_probes_cover_budgeted_kernels():
    budgets = rules.load_budgets()
    assert set(budgets["kernels"]) <= set(rules.kernel_probes())


def test_cost_family_gates_clean_on_repo(ctx):
    results = run_rules(ctx, families=["cost"])
    assert len(results) == 4
    assert all(r.status == "ok" for r in results), \
        [(r.rule, r.detail, [v.as_dict() for v in r.violations])
         for r in results]


# --------------------------------------------------------------------------
# budgets io
# --------------------------------------------------------------------------

def test_write_budgets_preserves_policy_sections(tmp_path, ctx):
    p = tmp_path / "budgets.json"
    first = rules.write_budgets(p, ctx)
    assert first["exponents"]["sqmd.build_graph_delta"] == 1.2

    # tighten a policy pin by hand, then re-baseline: the measured
    # scalars refresh but the pin must survive
    edited = json.loads(p.read_text())
    edited["exponents"]["sqmd.build_graph_delta"] = 1.05
    edited["entries"]["cohort_step"]["flops"] = 1.0
    p.write_text(json.dumps(edited))
    second = rules.write_budgets(p, ctx)
    assert second["exponents"]["sqmd.build_graph_delta"] == 1.05
    assert second["entries"]["cohort_step"]["flops"] == \
        first["entries"]["cohort_step"]["flops"]


def test_load_budgets_missing_file_errors(tmp_path):
    with pytest.raises(FileNotFoundError, match="--write-budgets"):
        rules.load_budgets(tmp_path / "nope.json")


def test_checked_in_budgets_match_entry_set():
    budgets = rules.load_budgets()
    assert set(budgets["entries"]) == set(entries.entry_names())
    assert set(budgets["exponents"]) == set(entries.SCALE_AXES)


# --------------------------------------------------------------------------
# analyze CLI: selection edge cases + json schema (PR 8 satellites)
# --------------------------------------------------------------------------

def _analyze(argv, capsys):
    from repro.launch.analyze import main
    code = main(argv)
    captured = capsys.readouterr()
    return code, captured.out, captured.err


def test_analyze_zero_selection_exits_nonzero(capsys):
    code, _, err = _analyze(["--families", "nosuchfamily"], capsys)
    assert code == 2 and "unknown rule family" in err

    code, _, err = _analyze(["--rules", "no-such-rule"], capsys)
    assert code == 2 and "unknown rule" in err

    # valid family x valid rule intersecting to nothing must also refuse
    code, _, err = _analyze(["--families", "cost", "--rules",
                             "bare-assert"], capsys)
    assert code == 2 and "matched zero rules" in err


def test_analyze_json_schema_pinned(capsys):
    code, out, _ = _analyze(["--families", "lint", "--json"], capsys)
    assert code == 0
    report = json.loads(out)
    assert set(report) == {"rules", "failed", "device_count"}
    assert report["failed"] is False
    for r in report["rules"]:
        assert {"rule", "family", "status", "n_findings", "detail",
                "suppressed", "violations"} <= set(r)
        assert r["family"] == "lint"
        assert r["n_findings"] == len(r["violations"])


def test_analyze_write_budgets_roundtrip(tmp_path, capsys):
    p = tmp_path / "b.json"
    code, _, err = _analyze(["--write-budgets", str(p)], capsys)
    assert code == 0 and "wrote cost budgets" in err
    assert set(json.loads(p.read_text())["entries"]) == \
        set(entries.entry_names())


def test_analyze_cost_table_prints(capsys):
    code, out, _ = _analyze(["--cost-table"], capsys)
    assert code == 0
    assert "sqmd.build_graph_delta" in out and "temp_bytes~n^" in out


# --------------------------------------------------------------------------
# benchmarks: cost_validate + trajectory
# --------------------------------------------------------------------------

def _shard_rows(step=(1.0, 2.0), graph=(1.0, 4.0)):
    rows = []
    for (n, st, gr) in zip((256, 1024), step, graph):
        rows.append({"n_clients": n, "devices": 1, "ref_size": 8,
                     "n_classes": 10, "batch": 3, "step_s": st,
                     "upload_s": st / 4, "graph_build_s": gr,
                     "steps_per_s": 1.0 / st})
    return rows


def test_cost_validate_rank_order_and_miss(tmp_path, capsys):
    cv = _load_script("cost_validate")
    good = tmp_path / "shard.json"
    good.write_text(json.dumps(_shard_rows()))
    out = tmp_path / "cost.json"
    code = cv.main(["--shard-json", str(good), "--out", str(out)])
    assert code == 0
    report = json.loads(out.read_text())
    assert report["rank_order_ok"] and report["n_pairs"] == 3
    for cell in report["cells"]:
        assert cell["predicted_s"] > 0

    # measurements ordered AGAINST N: the model must refuse to agree
    bad = tmp_path / "shard_bad.json"
    bad.write_text(json.dumps(_shard_rows(step=(2.0, 1.0),
                                          graph=(4.0, 1.0))))
    code = cv.main(["--shard-json", str(bad), "--smoke"])
    captured = capsys.readouterr()
    assert code == 1 and "RANK MISS" in captured.err
    assert not (tmp_path / "BENCH_cost.json").exists()  # smoke writes nothing

    assert cv.main(["--shard-json", str(tmp_path / "missing.json")]) == 2


def test_checked_in_bench_cost_ranks_every_shard_pair():
    # the acceptance artifact: BENCH_cost.json vs BENCH_shard.json
    report = json.loads((REPO_ROOT / "BENCH_cost.json").read_text())
    shard = json.loads((REPO_ROOT / "BENCH_shard.json").read_text())
    assert report["rank_order_ok"] is True
    assert report["n_rank_miss"] == 0
    n_cells = len(shard) * 3
    assert len(report["cells"]) == n_cells
    devices = {r["devices"] for r in shard}
    sizes = {r["n_clients"] for r in shard}
    pairs_expected = 3 * len(devices) * math.comb(len(sizes), 2)
    assert report["n_pairs"] == pairs_expected


def test_trajectory_aggregates_and_smoke(tmp_path, capsys):
    tj = _load_script("trajectory")
    (tmp_path / "BENCH_alpha.json").write_text(json.dumps([
        {"n_clients": 4, "devices": 1, "step_s": 0.5},
        {"n_clients": 8, "devices": 1, "step_s": 0.9},
        {"n_clients": 8, "devices": 1, "step_s": 0.91},   # collision
    ]))
    (tmp_path / "BENCH_beta.json").write_text(json.dumps(
        {"rows": [{"codec": "int8", "ratio": 3.5}], "acceptance": True}))

    code = tj.main(["--root", str(tmp_path)])
    assert code == 0
    traj = json.loads((tmp_path / "BENCH_trajectory.json").read_text())
    assert set(traj["benches"]) == {"alpha", "beta"}
    alpha = traj["benches"]["alpha"]
    assert alpha["n_clients=4,devices=1"] == {"step_s": 0.5}
    assert "n_clients=8,devices=1#1" in alpha            # kept, suffixed
    beta = traj["benches"]["beta"]
    assert beta["codec=int8"] == {"ratio": 3.5}
    assert beta["_summary"] == {"acceptance": True}
    # the aggregator must not re-ingest its own output
    assert "trajectory" not in traj["benches"]

    code = tj.main(["--root", str(tmp_path), "--smoke"])
    assert code == 0
    assert tj.main(["--root", str(tmp_path / "empty")]) == 2
    capsys.readouterr()


def test_trajectory_on_checked_in_benches():
    tj = _load_script("trajectory")
    traj = tj.build_trajectory(REPO_ROOT)
    assert {"shard", "cost", "wire", "serve",
            "server_scale"} <= set(traj["benches"])
    shard = traj["benches"]["shard"]
    key = "n_clients=256,devices=1,ref_size=64,n_classes=10,batch=16"
    assert "step_s" in shard[key]
