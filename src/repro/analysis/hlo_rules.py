"""HLO auditors: the zero-collective invariant + recompile bucketing.

``client-axis-collectives`` lowers the device-sharded hot paths
(``sharded_cohort_step``, the shard_map'd divergence rebuild) under the
forced 8-device host mesh and parses the PARTITIONED module text with
``launch/hlo_analysis.collective_bytes`` — the claim that the client axis
partitions with zero cross-device traffic stops being a benchmark
anecdote and becomes a CI assertion.

``jit-cache-bucketing`` replays a round schedule with varying upload
counts against the incremental graph update and reads the jit cache size
before/after: without power-of-two row bucketing
(``similarity._bucket_rows``) every distinct upload count is a fresh
compile (the PR 3 bucket class).
"""
from __future__ import annotations

from typing import Callable, Iterable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import fixtures
from repro.analysis.registry import AnalysisContext, Violation, register_rule
from repro.launch.hlo_analysis import collective_bytes


# --------------------------------------------------------------------------
# audit helpers
# --------------------------------------------------------------------------

def collective_violations(where: str, hlo_text: str,
                          rule: str = "client-axis-collectives"
                          ) -> List[Violation]:
    """One violation per collective kind present in the compiled text."""
    stats = collective_bytes(hlo_text)
    counts = stats["_counts"]
    raw = stats["_raw"]
    out = []
    for kind in sorted(counts):
        if counts[kind]:
            out.append(Violation(
                rule, f"{where}#{kind}",
                f"{counts[kind]} {kind} op(s) ({raw[kind]} operand bytes) "
                f"in a client-axis path that must partition with zero "
                f"collectives"))
    return out


def recompile_violations(where: str, jit_fn, replay: Callable[[], None],
                         max_new_compiles: int,
                         rule: str = "jit-cache-bucketing"
                         ) -> List[Violation]:
    """Run ``replay`` and compare ``jit_fn``'s cache growth against the
    bucketed expectation. ``_cache_size`` counts one entry per traced
    (shapes, statics) signature — growth beyond ``max_new_compiles``
    means the entry point retraces per call instead of per bucket."""
    before = jit_fn._cache_size()
    replay()
    grew = jit_fn._cache_size() - before
    if grew > max_new_compiles:
        return [Violation(
            rule, where,
            f"{grew} fresh compiles for a replay that should hit at most "
            f"{max_new_compiles} shape buckets — pad dynamic dimensions "
            f"to power-of-two buckets (similarity._bucket_rows idiom)")]
    return []


def _sharded_step_text(mesh) -> str:
    """Compiled (SPMD-partitioned) HLO of the 8-way sharded cohort step
    on a probe cohort with one client row per device."""
    from repro.core.client import sharded_cohort_step
    from repro.sharding import client_sharding

    (apply_fn, optimizer, params, opt_state, bx, by, ref_x, targets,
     trainable) = fixtures._probe_cohort_args(fixtures.N_ROWS)
    row = client_sharding(mesh)
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    args = (jax.device_put(params, row), jax.device_put(opt_state, row),
            jax.device_put(bx, row), jax.device_put(by, row),
            jax.device_put(ref_x, rep), jax.device_put(targets, row),
            jax.device_put(trainable, row))
    step = sharded_cohort_step(mesh)
    return step.lower(apply_fn, optimizer, *args, 0.5,
                      True).compile().as_text()


def _sharded_divergence_text(mesh) -> str:
    """Compiled HLO of the shard_map'd row-strip divergence rebuild."""
    from repro.core import similarity
    from repro.sharding import CLIENT_AXIS

    n_dev = int(mesh.shape[CLIENT_AXIS])
    logp = jax.nn.log_softmax(
        jax.random.normal(jax.random.key(5),
                          (n_dev * 2, fixtures.REF, fixtures.CLASSES)),
        axis=-1)
    fn = similarity._sharded_strip_fn(mesh, "jnp")
    return fn.lower(logp, logp).compile().as_text()


# --------------------------------------------------------------------------
# registered rules
# --------------------------------------------------------------------------

@register_rule("client-axis-collectives", family="hlo", requires_devices=8)
def client_axis_collectives(ctx: AnalysisContext) -> Iterable[Violation]:
    """Assert zero collectives in the compiled sharded cohort step and
    the sharded divergence rebuild (8-device host mesh)."""
    from repro.sharding import make_client_mesh
    mesh = make_client_mesh(8)
    yield from collective_violations("sharded_cohort_step",
                                     _sharded_step_text(mesh))
    yield from collective_violations("divergence_matrix[mesh]",
                                     _sharded_divergence_text(mesh))


# replayed upload counts vs their power-of-two buckets {1, 2, 4, 8}
_REPLAY_UPLOADS: Sequence[int] = (1, 2, 3, 5, 6, 7)
_REPLAY_BUCKETS = 4


@register_rule("jit-cache-bucketing", family="hlo")
def jit_cache_bucketing(ctx: AnalysisContext) -> Iterable[Violation]:
    """Replay a varying-upload-count schedule through the incremental
    divergence update; the jit cache must grow per BUCKET, not per
    distinct upload count."""
    from repro.core import similarity

    n, r, c = 16, 6, fixtures.CLASSES
    logp = jax.nn.log_softmax(
        jax.random.normal(jax.random.key(21), (n, r, c)) * 2.0, axis=-1)
    cache = similarity.divergence_matrix(logp)

    def replay() -> None:
        for u in _REPLAY_UPLOADS:
            mask = np.zeros(n, bool)
            mask[:u] = True
            similarity.update_divergence_cache(cache, logp, mask)

    yield from recompile_violations(
        "update_divergence_cache[jnp]", similarity._delta_update, replay,
        max_new_compiles=_REPLAY_BUCKETS)


@register_rule("serve-jit-bucketing", family="hlo")
def serve_jit_bucketing(ctx: AnalysisContext) -> Iterable[Violation]:
    """Replay every batch size 1..9 through the personalized serve step;
    the jit cache must grow per power-of-two bucket {1, 2, 4, 8, 16},
    not per distinct batch size."""
    from repro.models.mlp import MLPConfig, mlp_family
    from repro.serve import QueryEngine, SnapshotStore, serve_step

    n = 6
    init_fn, apply_fn = mlp_family(MLPConfig("probe-serve", 4, (8,), 3))
    params = jax.vmap(init_fn)(jax.random.split(jax.random.key(23), n))

    class _Cohort:
        family_name = "probe-serve"
        client_ids = np.arange(n)
    _Cohort.apply_fn = staticmethod(apply_fn)
    _Cohort.params = params

    class _Fed:
        n_clients = n
        cohorts = [_Cohort]

    store = SnapshotStore()
    store.publish(_Fed, t=0.0)
    qe = QueryEngine(store)

    def replay() -> None:
        for b in range(1, 10):
            qe.serve([i % n for i in range(b)],
                     np.zeros((b, 4), np.float32), t=0.0)

    yield from recompile_violations("serve.engine.serve_step", serve_step,
                                    replay, max_new_compiles=5)
