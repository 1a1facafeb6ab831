"""The ``cost`` rule family: perf budgets as CI gates.

Four rules over the static cost model (``interp``/``entries``/``model``):

  cost-budget        — every entry's flops / bytes / temp_bytes within a
                       tolerance band of the checked-in
                       ``cost_budgets.json``. The band is TWO-sided: a
                       regression fails, and so does a cost that fell far
                       below its budget (an inflated budget would hide
                       the next regression inside its slack).
  broadcast-blowup   — no materialized eqn output more than ``ratio``x
                       the size of all its inputs combined (fusion-aware;
                       generative fills from scalars exempt).
  superlinear-memory — the fitted leading exponent of each entry's
                       temporary-memory scaling stays within budget. This
                       is the rule that pins ``sqmd.build_graph_delta``
                       at Θ(u·N): anyone reintroducing a dense rebuild on
                       the delta path flips it to 'failed'.
  kernel-intensity   — arithmetic intensity of each kernel's oracle above
                       a roofline floor, with the model's dot FLOPs
                       cross-checked against the compiled HLO lowering
                       (``launch/hlo_cost``) of the very same function.

Budgets are policy + baseline in one file: the ``entries`` section is
measured (re-baseline with ``launch/analyze.py --write-budgets``); the
``exponents`` / ``kernels`` / ``blowup`` sections are hand-set policy and
are PRESERVED by a re-baseline — loosening the Θ(u·N) pin must be an
explicit edit, never a side effect of refreshing scalars.

Every rule body delegates to an audit helper that takes explicit inputs,
so the mutation suite can feed seeded-bug jaxprs/budgets through the same
code path CI runs (the PR 6 convention).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, List, Optional

from repro.analysis.cost import entries as entries_mod
from repro.analysis.cost import interp
from repro.analysis.cost import model
from repro.analysis.registry import (AnalysisContext, Violation,
                                     register_rule)

BUDGETS_PATH = Path(__file__).resolve().parent / "cost_budgets.json"

# hand-set policy: exponent ceilings per entry (temp_bytes leading
# exponent along the entry's scale axis) — build_graph_delta's 1.2 is the
# ROADMAP's million-client Θ(u·N) pin; the Θ(N²) entries get 2.15 (the
# exact-rebuild paths are ALLOWED to be quadratic, they must not get
# worse, e.g. an accidental (N,N,R) intermediate)
_POLICY_EXPONENTS: Dict[str, float] = {
    "cohort_step": 1.2,
    "cohort_messenger_upload": 1.2,
    "cohort_messenger_upload[int8]": 1.2,
    "sqmd.grade": 1.2,
    "sqmd.build_graph": 2.15,
    "sqmd.build_graph_delta": 1.2,
    "divergence_matrix": 2.15,
    "int8_dequant_kl": 2.15,
    # the IVF selection path must stay SUB-quadratic in N — candidates
    # scale ~n^{3/4} (probe · cluster size) and the coarse quantizer
    # ~n^{1/2}; a regression to dense (N,N) work trips these long before
    # it reaches 2.0
    "centroid_assign": 1.2,
    "ivf_search": 1.5,
    "serve_step": 1.2,
}


def _zoo_exponents() -> None:
    # every zoo family's cohort step must stay Θ(n) in clients — the vmap
    # over the cohort axis is embarrassingly parallel for EVERY
    # architecture, so a cross-client intermediate (an accidental (n,n,·)
    # attention or conv buffer) is a bug regardless of family
    from repro.models.zoo import registered_families
    for fam in registered_families():
        _POLICY_EXPONENTS[f"cohort_step[{fam}]"] = 1.2


_zoo_exponents()

# hand-set policy: roofline intensity floors (flops per argument+result
# byte) per kernel oracle — roughly half the measured intensity at the
# probe dims, so a kernel that loses its fusion (e.g. a dequant that
# round-trips fp32 through HBM twice) trips the floor
_POLICY_KERNELS: Dict[str, Dict[str, float]] = {
    "pairwise_kl": {"intensity_floor": 8.0},
    "pairwise_kl_pair": {"intensity_floor": 1.5},
    "int8_pairwise_kl": {"intensity_floor": 15.0},
    "soft_ce": {"intensity_floor": 1.0},
    # the K-sparse Eq. 5 is a gather and an elementwise sum: ~1 flop/byte
    "neighbor_mean": {"intensity_floor": 0.5},
    "neighbor_mean_dense": {"intensity_floor": 5.0},
}

# allow: sequence-adapter intermediates that LOOK like blowups at the
# tiny probe dims but are XLA-fusable and bounded by the adapter shapes —
# the patch-embed dot broadcasts (S, patch)·(patch, d) across the cohort
# axis, and the SSM causal-conv pad widens the channel axis before the
# depthwise conv; neither grows with n beyond the stacked batch itself
_POLICY_BLOWUP = {"ratio": 32.0, "floor_bytes": 4096, "allow": {
    "cohort_step[transformer]": ["dot_general"],
    "cohort_step[rglru]": ["dot_general"],
    "cohort_step[ssm]": ["dot_general", "pad"],
    # published widths over 1-token probe sequences: each weight's
    # gradient (n, d, f) dwarfs the inputs, and the SSD pads 1 token to
    # its chunk of 128
    "cohort_step[nemotron-h]": ["dot_general", "pad"],
}}
_DEFAULT_TOLERANCE = 0.35
_DEFAULT_HLO_BAND = 3.0


# --------------------------------------------------------------------------
# budgets io
# --------------------------------------------------------------------------

def load_budgets(path: Optional[Path] = None) -> dict:
    p = Path(path) if path else BUDGETS_PATH
    if not p.exists():
        raise FileNotFoundError(
            f"cost budgets not found: {p} — generate with "
            f"launch/analyze.py --write-budgets")
    return json.loads(p.read_text())


def compute_budgets(ctx: Optional[AnalysisContext] = None,
                    existing: Optional[dict] = None) -> dict:
    """Fresh budgets: measured ``entries`` scalars + policy sections kept
    from ``existing`` (or the module defaults for a first write)."""
    table = model.cost_table(ctx)
    old = existing or {}
    return {
        "dims": dict(entries_mod.DEFAULT_DIMS),
        "tolerance": old.get("tolerance", _DEFAULT_TOLERANCE),
        "entries": {name: {m: getattr(s, m) for m in model.METRICS}
                    for name, s in sorted(table.items())},
        # hand-tuned values in an existing budgets file win per key, but
        # entries new to the code still pick up their policy defaults —
        # a fresh entry must never ship without its ceiling
        "exponents": {**_POLICY_EXPONENTS, **old.get("exponents", {})},
        "kernels": {**_POLICY_KERNELS, **old.get("kernels", {})},
        "blowup": old.get("blowup", dict(_POLICY_BLOWUP)),
        "hlo_flops_band": old.get("hlo_flops_band", _DEFAULT_HLO_BAND),
    }


def write_budgets(path: Optional[Path] = None,
                  ctx: Optional[AnalysisContext] = None) -> dict:
    """(Re-)baseline the measured sections; returns what was written."""
    p = Path(path) if path else BUDGETS_PATH
    existing = json.loads(p.read_text()) if p.exists() else None
    budgets = compute_budgets(ctx, existing=existing)
    p.write_text(json.dumps(budgets, indent=2, sort_keys=True) + "\n")
    return budgets


def _ctx_budgets(ctx: AnalysisContext) -> dict:
    if "cost_budgets" not in ctx.cache:
        ctx.cache["cost_budgets"] = load_budgets()
    return ctx.cache["cost_budgets"]  # type: ignore[return-value]


# --------------------------------------------------------------------------
# audit helpers (mutation-testable: explicit inputs, no registry state)
# --------------------------------------------------------------------------

def budget_violations(table: Dict[str, interp.CostSummary],
                      budgets: dict,
                      rule: str = "cost-budget") -> List[Violation]:
    tol = float(budgets.get("tolerance", _DEFAULT_TOLERANCE))
    out: List[Violation] = []
    for name in sorted(budgets.get("entries", {})):
        per = budgets["entries"][name]
        s = table.get(name)
        if s is None:
            out.append(Violation(rule, name,
                                 "budgeted entry no longer traced — drop "
                                 "it with --write-budgets or restore the "
                                 "entry point"))
            continue
        for metric, budget in sorted(per.items()):
            val = float(getattr(s, metric))
            b = float(budget)
            if val > b * (1.0 + tol):
                out.append(Violation(
                    rule, f"{name}#{metric}",
                    f"{metric} {val:.3e} exceeds budget {b:.3e} "
                    f"(+{100 * (val / b - 1):.0f}%, band ±{tol:.0%}) — a "
                    f"cost regression, or re-baseline with "
                    f"--write-budgets"))
            elif b and val < b * (1.0 - tol):
                out.append(Violation(
                    rule, f"{name}#{metric}",
                    f"{metric} {val:.3e} fell below budget {b:.3e} "
                    f"(-{100 * (1 - val / b):.0f}%, band ±{tol:.0%}) — "
                    f"the budget is stale/inflated and would mask the "
                    f"next regression; re-baseline with --write-budgets"))
    for name in sorted(set(table) - set(budgets.get("entries", {}))):
        out.append(Violation(rule, name,
                             "entry traced but has no budget — add it "
                             "with --write-budgets"))
    return out


def exponent_violations(scaling: Dict[str, dict], exponents: Dict[str, float],
                        rule: str = "superlinear-memory") -> List[Violation]:
    out: List[Violation] = []
    for name in sorted(exponents):
        ceiling = float(exponents[name])
        rec = scaling.get(name)
        if rec is None:
            out.append(Violation(rule, name,
                                 "exponent-budgeted entry has no scaling "
                                 "sweep (SCALE_AXES)"))
            continue
        got = float(rec["temp_bytes"]["leading"])
        if got > ceiling:
            axis = rec["axis"]
            out.append(Violation(
                rule, name,
                f"temporary-memory scaling fitted Θ({axis}^{got:.2f}) "
                f"exceeds the budgeted Θ({axis}^{ceiling:.2f}) — samples "
                f"{['%.3e' % y for y in rec['temp_bytes']['samples']]} at "
                f"{axis}={rec['values']}"))
    return out


def blowup_violations(name: str, jaxpr, blowup: dict,
                      rule: str = "broadcast-blowup") -> List[Violation]:
    allow = blowup.get("allow", {}).get(name, ())
    found = interp.find_blowups(jaxpr,
                                ratio=float(blowup.get("ratio", 32.0)),
                                floor_bytes=int(blowup.get("floor_bytes",
                                                           4096)),
                                allow_prims=allow)
    return [Violation(
        rule, f"{name}#{b.prim}",
        f"{b.prim} materializes {b.out_nbytes} bytes from {b.ratio:.0f}x "
        f"smaller inputs: {b.eqn_str}") for b in found]


def intensity_violations(name: str, summary: interp.CostSummary,
                         floor: float, hlo_flops: Optional[float] = None,
                         band: float = _DEFAULT_HLO_BAND,
                         rule: str = "kernel-intensity") -> List[Violation]:
    out: List[Violation] = []
    got = summary.intensity
    if got < floor:
        out.append(Violation(
            rule, f"kernel.{name}",
            f"arithmetic intensity {got:.2f} flops/byte below the "
            f"roofline floor {floor:.2f} — the kernel's fused form lost "
            f"compute density (extra HBM round-trips?)"))
    model_dot = summary.flops_by_prim.get("dot_general", 0.0)
    if hlo_flops and model_dot:
        ratio = max(hlo_flops / model_dot, model_dot / hlo_flops)
        if ratio > band:
            out.append(Violation(
                rule, f"kernel.{name}#hlo-crosscheck",
                f"cost-model dot FLOPs {model_dot:.3e} vs compiled-HLO "
                f"FLOPs {hlo_flops:.3e} disagree by {ratio:.1f}x (band "
                f"{band:.1f}x) — the model no longer matches what XLA "
                f"actually lowers"))
    return out


# --------------------------------------------------------------------------
# kernel probes for kernel-intensity
# --------------------------------------------------------------------------

def kernel_probes() -> Dict[str, tuple]:
    """Kernel name -> (oracle fn, ShapeDtypeStruct args) at probe dims.
    The jnp oracles define each kernel's math; their traces price the
    kernel's work and their jit lowering is the HLO cross-check subject."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref

    d = entries_mod.DEFAULT_DIMS
    n, r, c, u, k = d["n"], d["r"], d["c"], d["q"], d["k"]
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    return {
        "pairwise_kl": (ref.pairwise_kl_ref, (f32(n, r, c),)),
        "pairwise_kl_pair": (ref.pairwise_kl_pair_ref,
                             (f32(u, r, c), f32(n, r, c))),
        "int8_pairwise_kl": (ref.int8_pairwise_kl_ref,
                             (jax.ShapeDtypeStruct((n, r, c), jnp.uint8),
                              f32(n, r), f32(n, r))),
        "soft_ce": (ref.soft_ce_ref,
                    (f32(n, r, c), jax.ShapeDtypeStruct((r,), jnp.int32))),
        "neighbor_mean": (ref.neighbor_mean_sparse_ref,
                          (jax.ShapeDtypeStruct((n, k), jnp.int32),
                           f32(n, k), f32(n, r, c))),
        "neighbor_mean_dense": (ref.neighbor_mean_ref,
                                (f32(n, n), f32(n, r, c))),
    }


def _kernel_hlo_flops(fn, args) -> float:
    import jax

    from repro.launch.hlo_cost import analyze_hlo_text
    text = jax.jit(fn).lower(*args).compile().as_text()
    return float(analyze_hlo_text(text).flops)


# --------------------------------------------------------------------------
# registered rules
# --------------------------------------------------------------------------

@register_rule("cost-budget", family="cost")
def cost_budget(ctx: AnalysisContext) -> Iterable[Violation]:
    """Every entry point's flops/bytes/temp_bytes within the tolerance
    band of the checked-in cost_budgets.json (two-sided)."""
    yield from budget_violations(model.cost_table(ctx), _ctx_budgets(ctx))


@register_rule("broadcast-blowup", family="cost")
def broadcast_blowup(ctx: AnalysisContext) -> Iterable[Violation]:
    """No materialized intermediate vastly larger than its inputs in any
    traced entry point (fusion-aware; kernel allowlist in budgets)."""
    blowup = _ctx_budgets(ctx).get("blowup", _POLICY_BLOWUP)
    for name in entries_mod.entry_names():
        yield from blowup_violations(name, entries_mod.trace_entry(name),
                                     blowup)


@register_rule("superlinear-memory", family="cost")
def superlinear_memory(ctx: AnalysisContext) -> Iterable[Violation]:
    """Fitted temporary-memory leading exponents within their budgeted
    ceilings — the Θ(u·N) pin on the delta graph path."""
    budgets = _ctx_budgets(ctx)
    yield from exponent_violations(model.scaling_report(ctx),
                                   budgets.get("exponents", {}))


@register_rule("kernel-intensity", family="cost")
def kernel_intensity(ctx: AnalysisContext) -> Iterable[Violation]:
    """Kernel-oracle arithmetic intensity above its roofline floor, with
    the model's dot FLOPs cross-checked against the compiled HLO."""
    import jax
    budgets = _ctx_budgets(ctx)
    band = float(budgets.get("hlo_flops_band", _DEFAULT_HLO_BAND))
    probes = kernel_probes()
    for name, spec in sorted(budgets.get("kernels", {}).items()):
        if name not in probes:
            yield Violation("kernel-intensity", f"kernel.{name}",
                            "budgeted kernel has no probe in "
                            "cost.rules.kernel_probes")
            continue
        fn, args = probes[name]
        summary = interp.summarize(jax.make_jaxpr(fn)(*args))
        hlo_flops = _kernel_hlo_flops(fn, args)
        yield from intensity_violations(
            name, summary, floor=float(spec.get("intensity_floor", 0.0)),
            hlo_flops=hlo_flops, band=band)
