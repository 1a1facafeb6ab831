"""Config-driven federation engine (Algorithm 1 end-to-end).

``FederationEngine`` owns the four moving parts the old free-function
driver hardwired together:

  * a ``Federation`` state bundle (cohorts + server state + targets),
  * a ``ServerPolicy`` strategy (grade / build_graph / emit_targets),
  * a client-availability ``Schedule`` (always-on, staged joins, dropout,
    stragglers, ...),
  * a ``FederationConfig`` (rounds, batch size, local steps, eval cadence,
    kernel backend) — the kernel ``backend`` is threaded from this single
    engine-owned setting into every server-side kernel call.

Round callbacks observe eval-time metrics (``cb(engine, rnd, metrics)``)
so benchmarks/dashboards hook in without subclassing.

Both engines are thin drivers over the event runtime
(``repro.core.runtime``): a ``ClientRuntime`` runs the gated local steps,
a ``ServerBus`` merges messenger uploads staleness-aware and fires policy
rounds per its ``Trigger``. ``FederationEngine`` is the synchronous
special case (``SyncClock`` + every-upload trigger — bit-identical
same-seed trajectories to the pre-runtime round loop);
``AsyncFederationEngine.fit(until=...)`` drives the full virtual-clock
event loop over an ``ArrivalProcess``.

Typical use::

    engine = FederationEngine.build(ds, splits, zoo, assignment,
                                    sqmd(q=16, k=8),
                                    config=FederationConfig(rounds=40))
    history = engine.fit(splits)

    async_engine = AsyncFederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=16, k=8),
        arrivals=StragglerLatency(fraction=0.3, delay=2.5),
        trigger=Quorum(frac=0.5))
    history = async_engine.fit(splits, until=40.0)

Messengers travel wire-encoded (``repro.core.wire``): the config's
``uplink``/``downlink`` codec names pick the format, the ServerBus
meters the bytes actually paid, and ``History.bytes_up``/``bytes_down``
expose the cumulative totals for bandwidth-vs-accuracy plots.
"""
from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import graph as graph_mod
from repro.core import wire
from repro.core.client import (Cohort, cohort_accuracy,
                               cohort_accuracy_masked, make_cohort)
from repro.core.policies import ServerPolicy, as_policy
from repro.core.protocols import Protocol
from repro.core.runtime import (ClientRuntime, Clock, ServerBus, SyncClock,
                                Trigger, as_trigger)
from repro.core.schedules import (ArrivalProcess, Schedule, StagedJoin,
                                  as_arrivals, as_schedule)
from repro.core.server import ServerState, init_server
from repro.data.partition import ClientSplit, pack_cohort
from repro.data.synthetic import FederatedDataset
from repro.obs import span
from repro.optim import Optimizer, sgd


@dataclasses.dataclass
class History:
    """Eval-time trajectory. ``rounds`` is the round index (sync) or the
    nearest virtual tick (async); ``times`` the virtual eval time, so
    async plots can show accuracy vs. virtual time, not just rounds.
    ``server_rounds`` counts policy rounds the ServerBus has fired by each
    eval; ``staleness`` the repository staleness histogram then.
    ``bytes_up``/``bytes_down`` are the CUMULATIVE wire bytes the
    federation has paid by each eval (summed over clients, metered by the
    ServerBus per encoded payload) — the x-axis of
    bandwidth-vs-accuracy plots."""
    rounds: List[int] = dataclasses.field(default_factory=list)
    mean_acc: List[float] = dataclasses.field(default_factory=list)
    per_client_acc: List[np.ndarray] = dataclasses.field(default_factory=list)
    val_acc: List[float] = dataclasses.field(default_factory=list)
    graph_stats: List[dict] = dataclasses.field(default_factory=list)
    mean_loss: List[float] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    server_rounds: List[int] = dataclasses.field(default_factory=list)
    staleness: List[dict] = dataclasses.field(default_factory=list)
    bytes_up: List[float] = dataclasses.field(default_factory=list)
    bytes_down: List[float] = dataclasses.field(default_factory=list)

    def final_metrics(self, mask: Optional[np.ndarray] = None) -> dict:
        acc = self.per_client_acc[-1]
        if mask is not None:
            acc = acc[mask]
        return {"acc": float(np.mean(acc)), "std": float(np.std(acc))}

    @property
    def best_round_idx(self) -> int:
        """Model selection by VALIDATION accuracy (test stays untouched)."""
        if self.val_acc:
            return int(np.argmax(self.val_acc))
        return len(self.mean_acc) - 1

    @property
    def selected_acc(self) -> float:
        return self.mean_acc[self.best_round_idx]

    def selected_per_client(self) -> np.ndarray:
        return self.per_client_acc[self.best_round_idx]


@dataclasses.dataclass
class Federation:
    """The pure state bundle (what checkpoints persist). Orchestration
    lives in FederationEngine."""
    cohorts: List[Cohort]
    server: ServerState
    protocol: Protocol
    ref_x: jnp.ndarray
    ref_y: jnp.ndarray
    optimizer: Optimizer
    n_clients: int
    static_weights: Optional[jnp.ndarray] = None   # ddist graph
    join_round: Optional[np.ndarray] = None        # (N,) async schedule
    targets: Optional[jnp.ndarray] = None          # (N,R,C)
    history: History = dataclasses.field(default_factory=History)
    rng: Any = None
    uplink: str = "dense32"     # wire codec names; part of the persisted
    downlink: str = "dense32"   # state so checkpoints restore the format

    def client_rows(self, cohort: Cohort) -> np.ndarray:
        return cohort.client_ids


@dataclasses.dataclass
class FederationConfig:
    """Everything the engine needs to run ``fit`` — one object instead of
    five keyword arguments repeated at every call site."""
    rounds: int = 40
    batch_size: int = 32
    local_steps: int = 1
    eval_every: int = 10
    backend: Optional[str] = None   # kernel backend for ALL server math
    delta_graph: bool = False       # incremental O(u·N) server graph
    # updates from the div_cache (policies that support it); off by
    # default — the full rebuild is the bit-exact oracle
    uplink: str = "dense32"         # messenger wire codec, client->server
    downlink: str = "dense32"       # K^n target wire codec, server->client
    devices: Optional[int] = None   # shard the client axis over this many
    # devices (cohort steps + server divergence rows); None = the
    # single-device legacy path, bit-identical to every pinned trajectory
    selection: str = "exact"        # neighbor selection: "exact" dense
    # (N,N) divergence, or "ivf" approximate top-K index (sub-quadratic;
    # requires delta_graph — only the incremental path has an index)
    verbose: bool = False

    def __post_init__(self):
        if self.rounds < 0:
            raise ValueError(f"rounds must be >= 0, got {self.rounds}")
        if self.selection not in ("exact", "ivf"):
            raise ValueError(f"selection must be 'exact' or 'ivf', got "
                             f"{self.selection!r}")
        if self.selection == "ivf" and not self.delta_graph:
            raise ValueError("selection='ivf' requires delta_graph=True: "
                             "the approximate index only exists on the "
                             "incremental build_graph_delta path")
        if self.devices is not None and self.devices < 1:
            raise ValueError(f"devices must be >= 1, got {self.devices}")
        if self.batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got "
                             f"{self.batch_size}")
        if self.local_steps < 1:
            raise ValueError(f"local_steps must be >= 1, got "
                             f"{self.local_steps}")
        if self.eval_every < 1:
            raise ValueError(f"eval_every must be >= 1, got "
                             f"{self.eval_every}")
        for which in ("uplink", "downlink"):
            try:
                wire.as_codec(getattr(self, which))
            except KeyError as e:
                raise ValueError(f"{which}: {e}") from None


RoundCallback = Callable[["FederationEngine", int, Dict[str, Any]], None]


def _build_mesh(config: FederationConfig):
    """Client mesh for ``config.devices`` (None => single-device path)."""
    if config.devices is None:
        return None
    from repro.sharding import make_client_mesh
    return make_client_mesh(config.devices)


def _init_federation(ds: FederatedDataset, splits: Sequence[ClientSplit],
                     families: Dict[str, Tuple[Callable, Callable]],
                     assignment: Union[None, str, Sequence[str]],
                     policy: Union[str, Protocol, ServerPolicy],
                     *, optimizer: Optional[Optimizer] = None, seed: int = 0,
                     schedule: Union[None, str, Schedule] = None,
                     join_round: Optional[Sequence[int]] = None
                     ) -> Tuple[Federation, ServerPolicy, Schedule]:
    """Shared state construction for both engines. families:
    {name: (init_fn, apply_fn)} (a plain dict or a ``repro.models.zoo.Zoo``
    carrying per-family default optimizers); assignment[n] = family of
    client n, or a spec string — ``"fam:w,..."`` weighted shares (the
    paper's Table-I #ResNet8/20/50 ratios) / ``"fam,fam"`` round-robin /
    None for round-robin over all families."""
    default_opt = optimizer or sgd(0.05, momentum=0.9)
    # per-family optimizer defaults ride along on zoo-built family maps;
    # an EXPLICIT optimizer argument overrides them federation-wide
    fam_opts: Dict[str, Optimizer] = {} if optimizer is not None else (
        getattr(families, "optimizers", None) or {})
    key = jax.random.key(seed)
    n = ds.n_clients
    if assignment is None or isinstance(assignment, str):
        from repro.models.zoo import parse_assignment
        assignment = parse_assignment(assignment, list(families), n)
    if len(assignment) != n:
        raise ValueError(f"assignment has {len(assignment)} entries for "
                         f"{n} clients")
    pol = as_policy(policy)
    cohorts = []
    for fam, (init_fn, apply_fn) in families.items():
        ids = [i for i in range(n) if assignment[i] == fam]
        if not ids:
            continue
        key, sub = jax.random.split(key)
        data = pack_cohort([splits[i] for i in ids])
        data = {k: jnp.asarray(v) for k, v in data.items()}
        cohorts.append(make_cohort(fam, init_fn, apply_fn,
                                   fam_opts.get(fam, default_opt),
                                   ids, data, sub))
    server = init_server(n, len(ds.ref_y), ds.n_classes)
    if type(pol).setup is not ServerPolicy.setup:
        # only policies with one-time state consume a key split, so
        # same-seed trajectories match the pre-engine driver exactly
        key, sub = jax.random.split(key)
        pol.setup(sub, n)
    sched = as_schedule(schedule, join_round=join_round)
    fed = Federation(
        cohorts=cohorts, server=server, protocol=pol.protocol,
        ref_x=jnp.asarray(ds.ref_x), ref_y=jnp.asarray(ds.ref_y),
        optimizer=default_opt, n_clients=n,
        static_weights=getattr(pol, "static_weights", None),
        join_round=(sched.join_round if isinstance(sched, StagedJoin)
                    else None),
        rng=key)
    return fed, pol, sched


def _record_metrics(eng, splits: Sequence[ClientSplit], rnd: int, t: float,
                    mask: np.ndarray) -> Dict[str, Any]:
    """Append one eval point to ``eng.history`` (shared by both engines)."""
    acc = eng.evaluate(splits)
    vacc = eng.evaluate(splits, which="val")
    h = eng.history
    h.rounds.append(rnd)
    h.times.append(float(t))
    h.per_client_acc.append(acc)
    h.mean_acc.append(float(acc[mask].mean()))
    h.val_acc.append(float(vacc[mask].mean()))
    h.server_rounds.append(eng.bus.n_triggers)
    stale = eng.bus.staleness(t)
    h.staleness.append(stale)
    h.bytes_up.append(float(eng.bus.bytes_up.sum()))
    h.bytes_down.append(float(eng.bus.bytes_down.sum()))
    metrics: Dict[str, Any] = {
        "round": rnd, "time": float(t), "acc": h.mean_acc[-1],
        "val_acc": h.val_acc[-1], "per_client_acc": acc, "joined": mask,
        "server_rounds": eng.bus.n_triggers, "staleness": stale,
        "bytes_up": h.bytes_up[-1], "bytes_down": h.bytes_down[-1],
    }
    if eng.last_graph is not None:
        # REAL stats from the policy's last-built graph — no fabricated
        # placeholder CollaborationGraph
        h.graph_stats.append(graph_mod.graph_stats(eng.last_graph))
        metrics["graph"] = h.graph_stats[-1]
    return metrics


class FederationEngine:
    """Policy- and schedule-agnostic federation driver — the synchronous
    special case of the event runtime (``SyncClock``, every-upload
    trigger, one wake per round for the schedule's availability mask)."""

    def __init__(self, federation: Federation,
                 policy: Union[None, str, Protocol, ServerPolicy] = None,
                 schedule: Union[None, str, Schedule] = None,
                 config: Optional[FederationConfig] = None,
                 callbacks: Sequence[RoundCallback] = ()):
        self.fed = federation
        self.policy = as_policy(policy if policy is not None
                                else federation.protocol,
                                static_weights=federation.static_weights)
        self.schedule = as_schedule(schedule,
                                    join_round=federation.join_round)
        self.config = config or FederationConfig()
        self.callbacks: List[RoundCallback] = list(callbacks)
        self.publish_hooks: List[Callable[[float], None]] = []
        self.clock: Clock = SyncClock()
        federation.uplink = self.config.uplink
        federation.downlink = self.config.downlink
        self.mesh = _build_mesh(self.config)
        self.clients = ClientRuntime(federation, self.policy, self.config,
                                     mesh=self.mesh)
        self.bus = ServerBus(federation, self.policy,
                             trigger="every-upload",
                             backend=self.config.backend,
                             delta=self.config.delta_graph,
                             mesh=self.mesh,
                             selection=self.config.selection)

    # -- convenience views -------------------------------------------------
    @property
    def server(self) -> ServerState:
        return self.fed.server

    @property
    def history(self) -> History:
        return self.fed.history

    @property
    def n_clients(self) -> int:
        return self.fed.n_clients

    @property
    def last_graph(self) -> Optional[graph_mod.CollaborationGraph]:
        return self.bus.last_graph

    def add_callback(self, cb: RoundCallback) -> None:
        self.callbacks.append(cb)

    # -- serving publish hooks ---------------------------------------------
    def attach_snapshots(self, store):
        """Publish versioned serving views of the per-client params into
        ``store`` (any object with ``publish(federation, t)`` — normally a
        ``repro.serve.SnapshotStore``): once immediately, then after every
        round (sync engine) / every wake and server fire (async engine).
        Returns the store for chaining."""
        self.publish_hooks.append(
            lambda t: store.publish(self.fed, t))
        store.publish(self.fed, float(self.clock.now))
        return store

    def _publish(self, t: float) -> None:
        for hook in self.publish_hooks:
            hook(float(t))

    # -- construction ------------------------------------------------------
    @classmethod
    def build(cls, ds: FederatedDataset, splits: Sequence[ClientSplit],
              families: Dict[str, Tuple[Callable, Callable]],
              assignment: Union[None, str, Sequence[str]],
              policy: Union[str, Protocol, ServerPolicy],
              *, config: Optional[FederationConfig] = None,
              schedule: Union[None, str, Schedule] = None,
              optimizer: Optional[Optimizer] = None, seed: int = 0,
              join_round: Optional[Sequence[int]] = None,
              callbacks: Sequence[RoundCallback] = ()) -> "FederationEngine":
        """families: {name: (init_fn, apply_fn)}; assignment[n] = family of
        client n, or a spec string (``"fam:w,..."`` weighted / ``"fam,fam"``
        round-robin / None — the paper's Table-I #ResNet8/20/50 ratios)."""
        fed, pol, sched = _init_federation(
            ds, splits, families, assignment, policy, optimizer=optimizer,
            seed=seed, schedule=schedule, join_round=join_round)
        return cls(fed, policy=pol, schedule=sched, config=config,
                   callbacks=callbacks)

    # -- one round ---------------------------------------------------------
    def run_round(self, rnd: int) -> None:
        """One federation round, in place: a full-federation wake for the
        schedule's availability mask, then (every ``interval`` rounds) an
        immediate zero-latency upload that fires the server round."""
        with span("repro.round", round=rnd):
            fed = self.fed
            t = float(rnd)
            self.clock.advance(t)
            avail_np = np.asarray(self.schedule.available(rnd,
                                                          fed.n_clients),
                                  bool)

            # --- local steps (line 12) ---
            use_ref = self.policy.uses_reference and rnd > 0
            self.clients.local_round(avail_np, use_ref)

            # --- communication step (lines 5-10) ---
            if (self.policy.uses_reference
                    and rnd % self.policy.interval == 0):
                msg = self.clients.collect_messengers(avail_np)
                self.bus.deliver(t, msg, avail_np)
            else:
                self.bus.observe(t, avail_np)
            self._publish(t)   # fresh params become the serving snapshot

    # -- evaluation --------------------------------------------------------
    def evaluate(self, splits: Sequence[ClientSplit],
                 which: str = "test") -> np.ndarray:
        return evaluate(self.fed, splits, which=which)

    def _record(self, splits: Sequence[ClientSplit], rnd: int
                ) -> Dict[str, Any]:
        mask = np.asarray(self.schedule.joined(rnd, self.n_clients), bool)
        if not mask.any():
            mask = np.ones_like(mask)
        return _record_metrics(self, splits, rnd, float(rnd), mask)

    # -- the training loop -------------------------------------------------
    def fit(self, splits: Sequence[ClientSplit]) -> History:
        cfg = self.config
        for rnd in range(cfg.rounds):
            self.run_round(rnd)
            if rnd % cfg.eval_every == 0 or rnd == cfg.rounds - 1:
                metrics = self._record(splits, rnd)
                for cb in self.callbacks:
                    cb(self, rnd, metrics)
                if cfg.verbose:
                    print(f"  round {rnd:4d}  "
                          f"acc={self.history.mean_acc[-1]:.4f}")
        return self.history


class AsyncFederationEngine:
    """Event-driven federation driver on a virtual clock.

    Clients wake per an ``ArrivalProcess`` (cadence/burst/latency model),
    messenger uploads travel with per-client latency and merge into the
    repository **on arrival** (stale rows persist until overwritten —
    merged, never dropped), and the ``ServerBus`` fires policy rounds per
    its ``Trigger`` (every-k uploads, wall interval, quorum, ...).

    ``fit(until=...)`` drains all events up to a virtual-time horizon and
    can be called again with a larger horizon to continue the same run;
    in-flight uploads scheduled past the horizon stay queued. Evals are
    recorded every ``config.eval_every`` virtual seconds plus at the
    horizon itself."""

    def __init__(self, federation: Federation,
                 policy: Union[None, str, Protocol, ServerPolicy] = None,
                 arrivals: Union[None, str, Schedule, ArrivalProcess] = None,
                 trigger: Union[None, str, Trigger] = None,
                 config: Optional[FederationConfig] = None,
                 callbacks: Sequence[RoundCallback] = ()):
        self.fed = federation
        self.policy = as_policy(policy if policy is not None
                                else federation.protocol,
                                static_weights=federation.static_weights)
        if self.policy.uses_reference and self.policy.interval != 1:
            raise ValueError(
                f"Protocol.interval={self.policy.interval} is a "
                f"round-synchronous concept; under the event clock express "
                f"server cadence with a Trigger instead (every-k, "
                f"interval, quorum)")
        self.arrivals = as_arrivals(arrivals)
        self.config = config or FederationConfig()
        self.callbacks: List[RoundCallback] = list(callbacks)
        self.publish_hooks: List[Callable[[float], None]] = []
        # extension point for non-training event kinds on the shared
        # clock (the serving runtime registers "query"/"serve-flush")
        self.handlers: Dict[str, Callable[[Any], None]] = {}
        self.clock = Clock()
        federation.uplink = self.config.uplink
        federation.downlink = self.config.downlink
        self.mesh = _build_mesh(self.config)
        self.clients = ClientRuntime(federation, self.policy, self.config,
                                     mesh=self.mesh)
        self.bus = ServerBus(federation, self.policy,
                             trigger=as_trigger(trigger),
                             backend=self.config.backend,
                             delta=self.config.delta_graph,
                             mesh=self.mesh,
                             selection=self.config.selection)
        self._seeded_until = -1.0

    # -- convenience views -------------------------------------------------
    server = FederationEngine.server
    history = FederationEngine.history
    n_clients = FederationEngine.n_clients
    last_graph = FederationEngine.last_graph
    add_callback = FederationEngine.add_callback
    evaluate = FederationEngine.evaluate
    attach_snapshots = FederationEngine.attach_snapshots
    _publish = FederationEngine._publish

    @classmethod
    def build(cls, ds: FederatedDataset, splits: Sequence[ClientSplit],
              families: Dict[str, Tuple[Callable, Callable]],
              assignment: Union[None, str, Sequence[str]],
              policy: Union[str, Protocol, ServerPolicy],
              *, arrivals: Union[None, str, Schedule, ArrivalProcess] = None,
              trigger: Union[None, str, Trigger] = None,
              config: Optional[FederationConfig] = None,
              optimizer: Optional[Optimizer] = None, seed: int = 0,
              callbacks: Sequence[RoundCallback] = ()
              ) -> "AsyncFederationEngine":
        fed, pol, _ = _init_federation(
            ds, splits, families, assignment, policy, optimizer=optimizer,
            seed=seed)
        return cls(fed, policy=pol, arrivals=arrivals, trigger=trigger,
                   config=config, callbacks=callbacks)

    # -- event seeding -----------------------------------------------------
    def _seed_events(self, until: float) -> None:
        lo = self._seeded_until
        n = self.n_clients
        for t, mask in self.arrivals.wakes(n, until):
            if t > lo:
                self.clock.schedule(t, "wake", np.asarray(mask, bool))
        period = self.bus.trigger.wall_period()
        if period is not None:
            k = max(0, int(np.floor(lo / period)) + 1)
            while k * period <= until + 1e-9:
                if k * period > lo:
                    self.clock.schedule(k * period, "server-tick")
                k += 1
        every = float(self.config.eval_every)
        k = max(0, int(np.floor(lo / every)) + 1)
        on_grid = False
        while k * every <= until + 1e-9:
            if k * every > lo:
                self.clock.schedule(k * every, "eval")
                on_grid = on_grid or abs(k * every - until) < 1e-9
            k += 1
        if not on_grid and until > lo:
            self.clock.schedule(until, "eval")   # terminal eval
        # never regress the watermark: a later fit() with a smaller
        # horizon must not re-seed (and replay) already-run events
        self._seeded_until = max(lo, until)

    # -- dispatch ----------------------------------------------------------
    def _dispatch(self, ev, splits: Sequence[ClientSplit]) -> None:
        t = ev.time
        if ev.kind == "wake":
            # an all-False wake still runs the (fully gated) local round
            # and a zero-row upload, so the RNG stream and server-round
            # cadence match the sync engine round for round
            mask = np.asarray(ev.payload, bool)
            use_ref = (self.policy.uses_reference
                       and self.bus.n_triggers > 0)
            self.clients.local_round(mask, use_ref)
            if self.policy.uses_reference:
                msg = self.clients.collect_messengers(mask)
                lat = np.asarray(
                    self.arrivals.latency(t, mask, self.n_clients), float)
                for d in (np.unique(lat[mask]) if mask.any() else [0.0]):
                    sub = mask & (lat == d) if mask.any() else mask
                    self.clock.schedule(t + float(d), "upload",
                                        (sub, msg, t))
            else:
                self.bus.observe(t, mask)
            self._publish(t)   # params moved: refresh the serving view
        elif ev.kind == "upload":
            sub, msg, produced_at = ev.payload
            if self.bus.deliver(t, msg, sub, produced_at=produced_at):
                self._publish(t)   # a server fire refreshed the targets
        elif ev.kind == "server-tick":
            if self.bus.tick(t):
                self._publish(t)
        elif ev.kind == "eval":
            self._record(splits, t)
        else:
            handler = self.handlers.get(ev.kind)
            if handler is None:
                raise ValueError(f"no handler for event kind {ev.kind!r} "
                                 f"(registered: "
                                 f"{sorted(self.handlers)})")
            handler(ev)

    def _record(self, splits: Sequence[ClientSplit], t: float) -> None:
        rnd = int(round(t))
        joined = self.arrivals.joined(t, self.n_clients)
        mask = (np.asarray(joined, bool) if joined is not None
                else self.clients.ever_woken.copy())
        if not mask.any():
            mask = np.ones(self.n_clients, bool)
        metrics = _record_metrics(self, splits, rnd, t, mask)
        for cb in self.callbacks:
            cb(self, rnd, metrics)
        if self.config.verbose:
            print(f"  t={t:7.2f}  acc={self.history.mean_acc[-1]:.4f}  "
                  f"server_rounds={self.bus.n_triggers}")

    # -- the event loop ----------------------------------------------------
    def fit(self, splits: Sequence[ClientSplit],
            until: Optional[float] = None) -> History:
        """Drain all events with virtual time <= ``until`` (default: the
        config's round budget, matching the sync engine's horizon)."""
        until = float(self.config.rounds - 1) if until is None \
            else float(until)
        self._seed_events(until)
        while (ev := self.clock.pop_due(until)) is not None:
            self._dispatch(ev, splits)
        return self.history


def _pad_cohort_shards(shard_x: List[np.ndarray], shard_y: List[np.ndarray]
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stack unequal-length shards: pad to the cohort max with zero rows /
    -1 labels and return (xs, ys, valid-mask). Truncating to the MIN (the
    old behaviour) silently dropped every longer client's tail samples."""
    m = max(len(y) for y in shard_y)
    lens = np.array([len(y) for y in shard_y])
    xs = np.stack([np.pad(np.asarray(x), [(0, m - len(x))]
                          + [(0, 0)] * (np.asarray(x).ndim - 1))
                   for x in shard_x])
    ys = np.stack([np.pad(np.asarray(y), (0, m - len(y)),
                          constant_values=-1) for y in shard_y])
    mask = np.arange(m)[None, :] < lens[:, None]
    return xs, ys, mask


def evaluate(fed: Federation, splits: Sequence[ClientSplit],
             which: str = "test") -> np.ndarray:
    """Per-client accuracy (N,) on the requested split. Cohorts with
    unequal shard lengths are padded + masked — no client's test samples
    are dropped. (Equal lengths keep the original unmasked kernel, which
    is the bit-exact path the pinned trajectories were captured on.)
    Device-sharded cohorts evaluate their REAL rows only (``real_params``
    slices the ghost padding off)."""
    accs = np.zeros(fed.n_clients)
    for coh in fed.cohorts:
        # getattr: duck-typed cohort stubs (tests) predate real_params
        params = getattr(coh, "real_params", coh.params)
        shard_x = [getattr(splits[i], f"{which}_x") for i in coh.client_ids]
        shard_y = [getattr(splits[i], f"{which}_y") for i in coh.client_ids]
        lens = {len(y) for y in shard_y}
        if len(lens) == 1:
            a = cohort_accuracy(coh.apply_fn, params,
                                jnp.asarray(np.stack(shard_x)),
                                jnp.asarray(np.stack(shard_y)))
        else:
            xs, ys, mask = _pad_cohort_shards(shard_x, shard_y)
            a = cohort_accuracy_masked(coh.apply_fn, params,
                                       jnp.asarray(xs), jnp.asarray(ys),
                                       jnp.asarray(mask))
        accs[coh.client_ids] = np.asarray(a)
    return accs


def precision_recall(fed: Federation, splits: Sequence[ClientSplit],
                     n_classes: int) -> Tuple[float, float]:
    """Macro precision/recall over all clients' test shards (Table III).
    Unequal shards are padded + masked, so every test sample counts."""
    from repro.core.client import cohort_pred
    tp = np.zeros(n_classes)
    fp = np.zeros(n_classes)
    fn = np.zeros(n_classes)
    for coh in fed.cohorts:
        xs, ys, mask = _pad_cohort_shards(
            [splits[i].test_x for i in coh.client_ids],
            [splits[i].test_y for i in coh.client_ids])
        pred = np.asarray(cohort_pred(coh.apply_fn,
                                      getattr(coh, "real_params",
                                              coh.params),
                                      jnp.asarray(xs)))
        for c in range(n_classes):
            tp[c] += np.sum((pred == c) & (ys == c) & mask)
            fp[c] += np.sum((pred == c) & (ys != c) & mask)
            fn[c] += np.sum((pred != c) & (ys == c) & mask)
    prec = np.mean(tp / np.maximum(tp + fp, 1))
    rec = np.mean(tp / np.maximum(tp + fn, 1))
    return float(prec), float(rec)
