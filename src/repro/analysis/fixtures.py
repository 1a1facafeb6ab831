"""Tiny probe fixtures + traced entry points for the analyzers.

The auditors inspect the REAL entry points (``core.client``'s cohort
step, the policy hooks, the wire codecs, the batch pipelines) — traced
once per run on deliberately tiny, deliberately odd-shaped inputs so

  * tracing is fast (milliseconds per entry point),
  * every structural dimension is DISTINCT (n_rows=8, n_real=5, batch=3,
    samples=11, ref=4, classes=3), so a shape showing up in a random
    draw unambiguously names the dimension it came from.

Everything is cached on the ``AnalysisContext`` so the jaxpr rules share
one trace per entry point.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# probe dimensions — all pairwise distinct (see module docstring)
N_CLIENTS = 6        # server population
N_ROWS = 8           # padded cohort rows (device-multiple)
N_REAL = 5           # real cohort rows under padding
BATCH = 3
SAMPLES = 11         # per-client shard length
REF = 4              # reference-set size
CLASSES = 3
FEATURES = 7


@dataclasses.dataclass
class TracedEntry:
    """One audited entry point: its closed jaxpr + audit metadata."""
    name: str
    jaxpr: object                      # jax.extend.core.ClosedJaxpr
    # inside the wire-codec boundary: precision drops are the point
    codec_boundary: bool = False
    # (padded_dim, real_dim) when the entry runs on a ghost-padded stack
    padded: Optional[Tuple[int, int]] = None


def _probe_family():
    from repro.models.mlp import MLPConfig, mlp_family
    return mlp_family(MLPConfig("probe", FEATURES, (8,), CLASSES))


def _probe_cohort_args(n_rows: int):
    """Stacked step inputs for an ``n_rows``-client probe cohort."""
    from repro.optim import adam
    init_fn, apply_fn = _probe_family()
    keys = jax.random.split(jax.random.key(7), n_rows)
    params = jax.vmap(init_fn)(keys)
    optimizer = adam(1e-3)
    opt_state = jax.vmap(optimizer.init)(params)
    bx = jnp.zeros((n_rows, BATCH, FEATURES), jnp.float32)
    by = jnp.zeros((n_rows, BATCH), jnp.int32)
    ref_x = jnp.zeros((REF, FEATURES), jnp.float32)
    targets = jnp.full((n_rows, REF, CLASSES), 1.0 / CLASSES, jnp.float32)
    trainable = jnp.ones((n_rows,), bool)
    return (apply_fn, optimizer, params, opt_state, bx, by, ref_x, targets,
            trainable)


def cohort_step_probe():
    """The raw (unjitted) cohort step + probe args, arranged for the
    masked-update audit: returns (wrapper, args, leaf_counts) where
    ``wrapper(params, opt_state, bx, by, ref_x, targets, trainable)``
    binds the static arguments and ``leaf_counts`` maps each positional
    arg to its flattened-leaf count (for invar-index bookkeeping)."""
    from repro.core import client
    (apply_fn, optimizer, params, opt_state, bx, by, ref_x, targets,
     trainable) = _probe_cohort_args(N_CLIENTS)

    def wrapper(params, opt_state, bx, by, ref_x, targets, trainable):
        return client._cohort_step(apply_fn, optimizer, params, opt_state,
                                   bx, by, ref_x, targets, trainable,
                                   0.5, True)

    args = (params, opt_state, bx, by, ref_x, targets, trainable)
    leaf_counts = [len(jax.tree.leaves(a)) for a in args]
    return wrapper, args, leaf_counts


def _probe_server():
    from repro.core.server import init_server, upload_messengers
    logp = jax.nn.log_softmax(
        jax.random.normal(jax.random.key(11),
                          (N_CLIENTS, REF, CLASSES)) * 2.0, axis=-1)
    st = init_server(N_CLIENTS, REF, CLASSES)
    st = upload_messengers(st, logp, jnp.ones((N_CLIENTS,), bool))
    # a warm divergence cache so the delta path has something to scatter
    # into (matches the engine: the cache tracks the repository)
    from repro.core import similarity
    st = st._replace(div_cache=similarity.divergence_matrix(st.repo_logp))
    labels = jax.random.randint(jax.random.key(12), (REF,), 0, CLASSES)
    return st, labels


def _sqmd_policy():
    from repro.core.policies.sqmd import SQMDPolicy
    from repro.core.protocols import Protocol
    return SQMDPolicy(Protocol("sqmd", q=4, k=2))


def build_entries(ctx) -> Dict[str, TracedEntry]:
    """Trace every audited entry point once; cached on the context."""
    if "entries" in ctx.cache:
        return ctx.cache["entries"]  # type: ignore[return-value]

    from repro.core import similarity, wire
    from repro.core.client import _cohort_messenger_upload
    from repro.data import pipeline
    from repro.core.graph import CollaborationGraph  # noqa: F401

    entries: Dict[str, TracedEntry] = {}

    def add(name: str, fn, *args, codec_boundary: bool = False,
            padded: Optional[Tuple[int, int]] = None) -> None:
        entries[name] = TracedEntry(name, jax.make_jaxpr(fn)(*args),
                                    codec_boundary=codec_boundary,
                                    padded=padded)

    # --- cohort step + messenger upload ----------------------------------
    wrapper, args, _ = cohort_step_probe()
    add("cohort_step", wrapper, *args)

    _, apply_fn = _probe_family()
    params = args[0]
    ref_x = args[4]
    add("cohort_messenger_upload",
        lambda p, rx: _cohort_messenger_upload(apply_fn, p, rx, codec=None),
        params, ref_x)
    add("cohort_messenger_upload[int8]",
        lambda p, rx: _cohort_messenger_upload(apply_fn, p, rx,
                                               codec=wire.Int8()),
        params, ref_x, codec_boundary=True)

    # --- server round pieces (policy hooks; the host platform's default
    # kernel path is the jnp oracle) --------------------------------------
    st, labels = _probe_server()
    pol = _sqmd_policy()
    add("sqmd.grade", pol.grade, st, labels)
    add("sqmd.build_graph", pol.build_graph,
        st, jnp.ones((N_CLIENTS,), jnp.float32))
    up_mask = np.zeros(N_CLIENTS, bool)
    up_mask[:2] = True
    add("sqmd.build_graph_delta",
        lambda s, q: pol.build_graph_delta(s, q, up_mask),
        st, jnp.ones((N_CLIENTS,), jnp.float32))
    graph = pol.build_graph(st, jnp.ones((N_CLIENTS,), jnp.float32))
    add("sqmd.emit_targets", pol.emit_targets, st, graph)

    # --- similarity paths -------------------------------------------------
    add("divergence_matrix", similarity.divergence_matrix, st.repo_logp)

    # --- wire codecs (the sanctioned precision boundary) ------------------
    probe_logp = st.repo_logp
    for codec_name in ("dense16", "int8", "topk:2"):
        codec = wire.as_codec(codec_name)
        add(f"wire[{codec_name}].roundtrip",
            lambda x, c=codec: c.decode(c.encode(x, domain="log")),
            probe_logp, codec_boundary=True)

    # --- batch pipelines (PRNG discipline) --------------------------------
    data = {"x": jnp.zeros((N_CLIENTS, SAMPLES, FEATURES), jnp.float32),
            "y": jnp.zeros((N_CLIENTS, SAMPLES), jnp.int32)}
    add("cohort_batch",
        lambda k, d: pipeline.cohort_batch(k, d, BATCH),
        jax.random.key(3), data)
    pdata = {"x": jnp.zeros((N_ROWS, SAMPLES, FEATURES), jnp.float32),
             "y": jnp.zeros((N_ROWS, SAMPLES), jnp.int32)}
    add("cohort_batch_padded",
        functools.partial(pipeline.cohort_batch_padded.__wrapped__,
                          batch_size=BATCH, n_real=N_REAL),
        jax.random.key(3), pdata, padded=(N_ROWS, N_REAL))

    ctx.cache["entries"] = entries
    return entries


def entry_names(ctx) -> List[str]:
    return sorted(build_entries(ctx))
