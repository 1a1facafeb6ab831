"""Event-driven virtual-time runtime: Clock/Event, server Triggers, and
the ClientRuntime / ServerBus halves of the federation.

The paper's reliability claim is about *asynchrony*: messengers arrive
stale, clients tick at their own cadence, and the server's dynamic graph
absorbs whatever the repository holds (``upload_messengers`` keeps stale
rows — they are merged, never dropped). This module gives that a
first-class time model:

  * ``Clock``   — a monotone virtual clock with a deterministic event
    queue (ties break by event-kind priority, then FIFO). ``SyncClock``
    is the degenerate round-synchronous case: time == round index.
  * ``ClientRuntime`` — wraps the Federation's cohorts; a wake mask picks
    which clients run gated vmapped local steps and produce messengers
    (the rest stay frozen — exactly the sync engine's semantics).
  * ``ServerBus`` — receives ``MessengerUpload`` deliveries at arbitrary
    virtual times, merges them staleness-aware into ``ServerState``, and
    fires ``policy_round`` when its ``Trigger`` says so: after every
    upload (the sync special case), every K uploads, on a wall-clock
    interval, or on a quorum of distinct uploaders.

``FederationEngine`` composes these with a ``SyncClock`` + every-upload
trigger (bit-identical same-seed trajectories to the round loop it
replaced); ``AsyncFederationEngine.fit(until=...)`` drives the full event
loop over an ``ArrivalProcess`` (``repro.core.schedules``).
"""
from __future__ import annotations

import abc
import dataclasses
import heapq
from typing import Any, Dict, List, Optional, Tuple, Type, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import wire
from repro.core.client import (cohort_messenger_upload, cohort_step,
                               expert_cohort_step, sharded_cohort_step,
                               sharded_messenger_upload)
from repro.core.server import (policy_round, staleness_summary,
                               upload_messengers)
from repro.data.pipeline import cohort_batch, cohort_batch_padded
from repro.obs import Counters, host_read, span

# --------------------------------------------------------------------------
# Clock / Event
# --------------------------------------------------------------------------

# Same-instant ordering: uploads merge before the server's wall tick looks
# at the repository, wakes train after the server settles, evals observe
# the fully-settled instant. Serving events (repro.serve) come last:
# queries admitted at t must see the instant's fully-settled snapshot,
# and flush deadlines release after the queries they batch.
_KIND_PRIORITY = {"upload": 0, "server-tick": 1, "wake": 2, "eval": 3,
                  "query": 4, "serve-flush": 5}


@dataclasses.dataclass(frozen=True)
class Event:
    time: float
    kind: str
    payload: Any = None


class Clock:
    """Monotone virtual clock + deterministic event queue."""

    def __init__(self, t0: float = 0.0):
        self.now = float(t0)
        self._heap: List[Tuple[float, int, int, Event]] = []
        self._seq = 0

    def schedule(self, time: float, kind: str, payload: Any = None) -> None:
        if time < self.now - 1e-9:
            raise ValueError(f"cannot schedule {kind!r} at t={time} in the "
                             f"past (now={self.now})")
        ev = Event(float(time), kind, payload)
        heapq.heappush(self._heap, (ev.time, _KIND_PRIORITY.get(kind, 9),
                                    self._seq, ev))
        self._seq += 1

    def pop_due(self, until: float) -> Optional[Event]:
        """Pop the next event with time <= until and advance ``now`` to it;
        None when nothing is due (later events stay queued)."""
        if self._heap and self._heap[0][0] <= until + 1e-9:
            ev = heapq.heappop(self._heap)[3]
            self.now = max(self.now, ev.time)
            return ev
        return None

    def peek_time(self) -> Optional[float]:
        return self._heap[0][0] if self._heap else None

    def advance(self, t: float) -> None:
        self.now = max(self.now, float(t))

    def __len__(self) -> int:
        return len(self._heap)


class SyncClock(Clock):
    """The round-synchronous degenerate clock: virtual time is the round
    index and no events queue — ``FederationEngine`` advances it as it
    loops."""


# --------------------------------------------------------------------------
# Server triggers
# --------------------------------------------------------------------------

_TRIGGERS: Dict[str, Type["Trigger"]] = {}


def register_trigger(name: str):
    def deco(cls: Type["Trigger"]) -> Type["Trigger"]:
        if name in _TRIGGERS:
            raise ValueError(f"trigger {name!r} already registered")
        cls.name = name
        _TRIGGERS[name] = cls
        return cls

    return deco


def registered_triggers() -> Tuple[str, ...]:
    return tuple(sorted(_TRIGGERS))


def get_trigger(name: str) -> Type["Trigger"]:
    try:
        return _TRIGGERS[name]
    except KeyError:
        raise KeyError(f"unknown trigger {name!r}; registered: "
                       f"{registered_triggers()}") from None


class Trigger(abc.ABC):
    """When the ServerBus runs ``policy_round``. Stateless predicates over
    the bus's upload counters, so triggers compose with any policy."""

    name: str = "?"

    def should_fire(self, t: float, bus: "ServerBus") -> bool:
        """Checked after every upload delivery."""
        return False

    def should_fire_on_tick(self, t: float, bus: "ServerBus") -> bool:
        """Checked at wall ticks (only for triggers with a period)."""
        return False

    def wall_period(self) -> Optional[float]:
        """Virtual-time period between server ticks, or None."""
        return None

    def __repr__(self) -> str:
        return f"{type(self).__name__}()"


@register_trigger("every-upload")
class EveryUpload(Trigger):
    """Fire after every delivery — ``FederationEngine``'s sync special
    case (one upload batch per communication round)."""

    def should_fire(self, t: float, bus: "ServerBus") -> bool:
        return True


@register_trigger("every-k")
class EveryKUploads(Trigger):
    """Fire once ``k`` client-rows have merged since the last fire."""

    def __init__(self, k: int = 8):
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.k = k

    def should_fire(self, t: float, bus: "ServerBus") -> bool:
        return bus.uploads_since_fire >= self.k

    def __repr__(self) -> str:
        return f"EveryKUploads(k={self.k})"


@register_trigger("interval")
class WallInterval(Trigger):
    """Fire on a virtual-time cadence (every ``period``), provided at
    least one upload arrived since the last fire."""

    def __init__(self, period: float = 1.0):
        if period <= 0:
            raise ValueError(f"period must be > 0, got {period}")
        self.period = float(period)

    def wall_period(self) -> Optional[float]:
        return self.period

    def should_fire_on_tick(self, t: float, bus: "ServerBus") -> bool:
        return True

    def __repr__(self) -> str:
        return f"WallInterval(period={self.period})"


@register_trigger("quorum")
class Quorum(Trigger):
    """Fire once a quorum of *distinct* clients has uploaded since the
    last fire — ``count`` absolute, else ``ceil(frac * n_clients)``."""

    def __init__(self, count: Optional[int] = None, frac: float = 0.5):
        if count is not None and count < 1:
            raise ValueError(f"count must be >= 1, got {count}")
        if not 0.0 < frac <= 1.0:
            raise ValueError(f"frac must be in (0, 1], got {frac}")
        self.count = count
        self.frac = frac

    def needed(self, n_clients: int) -> int:
        if self.count is not None:
            return self.count
        return max(1, int(np.ceil(self.frac * n_clients)))

    def should_fire(self, t: float, bus: "ServerBus") -> bool:
        return (int(bus.fresh_since_fire.sum())
                >= self.needed(bus.fed.n_clients))

    def __repr__(self) -> str:
        return (f"Quorum(count={self.count})" if self.count is not None
                else f"Quorum(frac={self.frac})")


def as_trigger(trigger: Union[None, str, Trigger]) -> Trigger:
    """Coerce None/name/instance into a Trigger (None => every-upload)."""
    if isinstance(trigger, Trigger):
        return trigger
    if isinstance(trigger, str):
        return get_trigger(trigger)()
    return EveryUpload()


# --------------------------------------------------------------------------
# ClientRuntime — the client half
# --------------------------------------------------------------------------

class ClientRuntime:
    """Runs the cohorts' gated local steps and produces messengers.

    One wake = ``config.local_steps`` vmapped SGD steps for every client in
    the mask (clients outside it stay frozen, params and optimizer state).
    RNG consumption order (one split per cohort per step, cohorts in build
    order) is identical to the old round loop, which is what makes the
    sync engine bit-identical on the same seed.

    Messengers leave here wire-encoded: each cohort's upload fuses its
    forward pass with the ``uplink`` codec's encode, and
    ``collect_messengers`` assembles the per-cohort Payloads into one
    N-stack Payload (the unit the ServerBus meters and decodes).

    With a client ``mesh`` the cohorts execute device-sharded: each
    cohort's stacks are ghost-padded to a device multiple and placed
    row-sharded over the mesh once at construction, every step runs
    through the mesh-pinned jits, and ghost rows stay permanently outside
    the trainable mask (bit-exact no-ops — the PR 3 frozen-client
    guarantee). Batch indices are drawn at the REAL cohort size, so the
    sharded run consumes the identical RNG stream as ``mesh=None``.

    ``counters`` sums on the device what the steps count: for a family
    with expert layers, ``expert_load.<family>`` (n_expert_layers, held)
    token choices per held expert, over its real clients."""

    def __init__(self, federation, policy, config, mesh=None):
        self.fed = federation
        self.policy = policy
        self.config = config
        self.mesh = mesh
        self.counters = Counters()
        self.ever_woken = np.zeros(federation.n_clients, bool)
        if mesh is not None:
            from repro.sharding import cohort_mesh, place_cohort_stacks
            for coh in federation.cohorts:
                if coh.sharding is None:
                    # each arch bucket gets its own (sub)mesh: buckets
                    # smaller than the device count live on a device
                    # subset instead of ghost-padding up to it
                    place_cohort_stacks(coh, cohort_mesh(mesh,
                                                         coh.n_clients))

    @property
    def uplink(self) -> wire.Codec:
        """Resolved from the Federation state bundle (the engine seeds it
        from the config; a checkpoint restore may overwrite it), so a
        resumed run really speaks the restored format."""
        return wire.as_codec(getattr(self.fed, "uplink", None))

    def local_round(self, mask_np: np.ndarray, use_ref: bool) -> None:
        """One local round for the masked clients, in place."""
        fed, cfg = self.fed, self.config
        with span("repro.local_round"):
            n, r, c = fed.server.repo_logp.shape
            if fed.targets is None:
                fed.targets = jnp.full((n, r, c), 1.0 / c, jnp.float32)
            self.ever_woken |= mask_np
            avail = jnp.asarray(mask_np)
            rows = cfg.batch_size + (len(fed.ref_x) if use_ref else 0)
            for _ in range(cfg.local_steps):
                for coh in fed.cohorts:
                    with span("repro.cohort_step", family=coh.family_name,
                              clients=coh.n_clients, **coh.step_args(rows)):
                        self._step_cohort(coh, avail, use_ref)

    def _step_cohort(self, coh, avail: jnp.ndarray, use_ref: bool) -> None:
        """One cohort's step: draw its batch, gather its availability and
        targets, and dispatch the jitted step."""
        fed, cfg = self.fed, self.config
        # cohorts are independently placed: each runs on its own
        # (sub)mesh's pinned jit; per-family optimizers override the
        # federation-wide default when the zoo set them
        step = (cohort_step if coh.sharding is None
                else sharded_cohort_step(coh.sharding.mesh))
        opt = coh.optimizer or fed.optimizer
        with span("repro.cohort_batch", family=coh.family_name):
            fed.rng, sub = jax.random.split(fed.rng)
            if coh.n_pad == 0:
                batch = cohort_batch(sub, coh.data, cfg.batch_size)
                rows = jnp.asarray(coh.client_ids)
                on = avail[rows]
            else:
                batch = cohort_batch_padded(sub, coh.data, cfg.batch_size,
                                            coh.n_clients)
                rows = jnp.asarray(coh.padded_ids)
                # ghost rows alias the last real client's global id;
                # force them out of the trainable mask regardless
                on = avail[rows] & (jnp.arange(coh.n_rows)
                                    < coh.n_clients)
            tgt = fed.targets[rows]
            if (self.mesh is not None and coh.sharding is not None
                    and coh.sharding.mesh.devices.size
                    < self.mesh.devices.size):
                # tiny bucket on a device subset: the target rows may be
                # committed to the FULL device set (the server emits
                # mesh-wide); re-place them on the bucket's submesh so
                # the pinned jit sees one device set
                tgt = jax.device_put(tgt, coh.sharding)
        args = (coh.apply_fn, opt, coh.params, coh.opt_state,
                batch["x"], batch["y"], fed.ref_x, tgt,
                on, self.policy.rho, use_ref)
        if coh.has_experts and coh.sharding is None:
            coh.params, coh.opt_state, _, counts = expert_cohort_step(*args)
            self.counters.add(f"expert_load.{coh.family_name}",
                              jnp.sum(counts[:coh.n_clients], axis=0))
        else:
            coh.params, coh.opt_state, _ = step(*args)

    def collect_messengers(self,
                           mask_np: Optional[np.ndarray] = None
                           ) -> wire.Payload:
        """Wire-encoded (N,R,C) messenger batch; cohorts with no masked
        client are skipped (their rows stay zero in the payload and are
        masked out of the merge anyway)."""
        fed = self.fed
        n, r, c = fed.server.repo_logp.shape
        parts, rows = [], []
        with span("repro.collect_messengers"):
            for coh in fed.cohorts:
                if mask_np is not None and not mask_np[coh.client_ids].any():
                    continue
                with span("repro.upload", family=coh.family_name):
                    parts.append(self._upload(coh))
                rows.append(coh.client_ids)
            if not parts:
                return self.uplink.encode(jnp.zeros((n, r, c), jnp.float32))
            with span("repro.assemble"):
                return wire.assemble(parts, rows, n)

    def _upload(self, coh) -> wire.Payload:
        """One cohort's wire-encoded messengers, real clients only."""
        up = (cohort_messenger_upload if coh.sharding is None
              else sharded_messenger_upload(coh.sharding.mesh))
        part = up(coh.apply_fn, coh.params, self.fed.ref_x,
                  codec=self.uplink)
        if coh.n_pad:
            # ghost rows never upload: slice the payload back to the
            # real clients before it enters the N-stack
            part = wire.gather(part, np.arange(coh.n_clients))
        if (self.mesh is not None and coh.sharding is not None
                and coh.sharding.mesh.devices.size
                < self.mesh.devices.size):
            # tiny-bucket payloads live on a device subset; replicate
            # them over the full mesh so the N-stack scatter sees one
            # device set across all cohorts
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(self.mesh, PartitionSpec())
            part = wire.Payload(
                part.codec, part.domain, part.shape,
                {k: jax.device_put(a, rep) for k, a in part.arrays.items()})
        return part


# --------------------------------------------------------------------------
# ServerBus — the server half
# --------------------------------------------------------------------------

class ServerBus:
    """Absorbs messenger uploads at arbitrary virtual times and fires
    policy rounds per its trigger.

    ``deliver`` merges the masked rows into the repository via
    ``upload_messengers`` — rows of clients not in the mask keep their
    stale value (merged, never dropped) — then asks the trigger whether to
    run ``policy_round``. ``tick`` is the wall-interval hook.
    ``staleness`` summarizes every repository row's virtual age since its
    newest merge, when asked (the engines ask at eval time).

    ``delta=True`` hands each fire the accumulated fresh-uploader mask so
    the policy can take its incremental O(u·N) graph update
    (``build_graph_delta``) instead of the O(N²) full rebuild —
    ``fresh_since_fire`` is exactly the set of repository rows that
    changed since the cache was last valid. Off by default: the full
    rebuild stays the bit-exact oracle.

    Bandwidth is metered where it is paid: ``deliver`` decodes the
    uplink Payload on ingest and adds its per-messenger wire bytes to
    ``bytes_up`` for every transmitting client (superseded out-of-order
    uploads still burned the link, so they still count); ``fire``
    wire-codes the policy's K^n targets with the ``downlink`` codec —
    training consumes the DECODED payload, so a lossy downlink really
    costs fidelity — and charges ``bytes_down`` to the receiving
    clients."""

    def __init__(self, federation, policy, trigger: Union[None, str,
                                                          Trigger] = None,
                 delta: bool = False,
                 uplink: Union[None, str, wire.Codec] = None,
                 downlink: Union[None, str, wire.Codec] = None,
                 mesh=None, selection: Optional[str] = None):
        self.fed = federation
        self.policy = policy
        self.trigger = as_trigger(trigger)
        self.delta = bool(delta)
        self.mesh = mesh
        if mesh is not None:
            # policies that shard their graph build read the mesh off
            # themselves (attribute, not hook kwarg — see ServerPolicy)
            policy.mesh = mesh
        if selection is not None:
            # same attribute pattern as mesh: the neighbor-selection
            # strategy ("exact" dense matrix vs "ivf" approximate index)
            # rides on the policy so build_graph_delta overrides keep
            # their signature
            policy.selection = selection
        # None => follow the Federation state bundle (engine-seeded,
        # checkpoint-restorable); an explicit codec pins this bus
        self._uplink = uplink
        self._downlink = downlink
        n = federation.n_clients
        self.last_upload_t = np.full(n, -np.inf)
        self.uploads_since_fire = 0                 # rows merged
        self.fresh_since_fire = np.zeros(n, bool)   # distinct uploaders
        self.n_uploads = 0
        self.n_triggers = 0
        self.bytes_up = np.zeros(n)    # cumulative uplink wire bytes
        self.bytes_down = np.zeros(n)  # cumulative downlink wire bytes
        self.last_graph = None

    @property
    def uplink(self) -> wire.Codec:
        return wire.as_codec(self._uplink if self._uplink is not None
                             else getattr(self.fed, "uplink", None))

    @property
    def downlink(self) -> wire.Codec:
        return wire.as_codec(self._downlink if self._downlink is not None
                             else getattr(self.fed, "downlink", None))

    def deliver(self, t: float,
                msg: Union[jnp.ndarray, wire.Payload],
                uploaded: np.ndarray,
                produced_at: Optional[float] = None) -> bool:
        """Merge one upload batch arriving at time ``t``; returns True if
        the trigger fired a policy round. ``msg`` is normally the wire
        Payload the clients encoded; a raw (N,R,C) array is put on the
        wire here (encoded with the bus's uplink codec) so every ingest
        pays — and meters — real payload bytes. ``produced_at`` is when
        the messengers were computed (default ``t``) — a latency-delayed
        upload merges already stale, and staleness tracks the content's
        age, not the arrival instant. Newest content wins per row: an
        out-of-order arrival older than what a row already holds is
        superseded and skipped (it would *regress* the repository — this
        is not the stale-row-keeping, which is about rows nobody
        refreshed). The trigger is consulted even for an empty batch, so
        an every-upload (sync) communication round with no available
        client still fires its policy round."""
        sent = np.asarray(uploaded, bool)
        pt = t if produced_at is None else produced_at
        up = sent & (pt >= self.last_upload_t)
        k = int(up.sum())
        with span("repro.deliver", rows=k, fire=self.n_triggers):
            if not isinstance(msg, wire.Payload):
                msg = self.uplink.encode(jnp.asarray(msg))
            self.bytes_up[sent] += wire.bytes_per_messenger(msg)
            fed = self.fed
            fed.server = upload_messengers(fed.server, msg, jnp.asarray(up))
            self.last_upload_t = np.where(up, pt, self.last_upload_t)
            self.n_uploads += k
            self.uploads_since_fire += k
            self.fresh_since_fire |= up
            if self.trigger.should_fire(t, self):
                self.fire(t)
                return True
            return False

    def tick(self, t: float) -> bool:
        """Wall tick: fire if the trigger wants to and new uploads exist
        (an unchanged repository would just recompute the same graph)."""
        if self.uploads_since_fire and self.trigger.should_fire_on_tick(
                t, self):
            self.fire(t)
            return True
        return False

    def fire(self, t: float) -> None:
        """Run policy_round now: grade -> build graph -> emit targets,
        then put the targets on the downlink wire — clients train on the
        DECODED payload, and its bytes are charged to the policy's
        receiver set (K^n payloads per client)."""
        fed = self.fed
        with span("repro.fire", fire=self.n_triggers, delta=self.delta,
                  rows=int(self.fresh_since_fire.sum())):
            uploaded = self.fresh_since_fire.copy() if self.delta else None
            fed.server, targets, self.last_graph = policy_round(
                fed.server, self.policy, fed.ref_y, uploaded=uploaded)
            with span("repro.downlink"):
                payload = self.downlink.encode(targets, domain="prob")
                decoded = wire.decode(payload)
            recv = host_read(self.policy.receivers(fed.server,
                                                   self.last_graph),
                             "fire.receivers", bool)
            if not recv.all():
                # nothing is sent to excluded rows, so nothing must
                # arrive: a lossy decode would otherwise turn their zero
                # target rows into spurious near-uniform distributions
                # they train toward
                decoded = jnp.where(jnp.asarray(recv)[:, None, None],
                                    decoded, 0.0)
            fed.targets = decoded
            self.bytes_down[recv] += wire.bytes_per_messenger(payload)
            self.n_triggers += 1
            self.uploads_since_fire = 0
            self.fresh_since_fire[:] = False

    def observe(self, t: float, mask_np: np.ndarray) -> None:
        """Non-communication round: mark the masked clients active and
        advance the server's round counter (the sync engine's off-interval
        branch, and the whole story for reference-free policies)."""
        fed = self.fed
        fed.server = fed.server._replace(
            active=fed.server.active | jnp.asarray(np.asarray(mask_np,
                                                              bool)),
            round=fed.server.round + 1)

    def staleness(self, now: float) -> dict:
        return staleness_summary(self.last_upload_t,
                                 np.asarray(self.fed.server.active, bool),
                                 now)

    # -- checkpointable state ----------------------------------------------
    def state_dict(self) -> dict:
        """The bus's trigger/staleness bookkeeping, as plain arrays/ints
        (what ``save_federation`` persists). Without it, a restored
        every-k/quorum bus double-fires or skips its first round and
        staleness summaries restart from -inf."""
        return {
            "last_upload_t": np.asarray(self.last_upload_t, float),
            "uploads_since_fire": int(self.uploads_since_fire),
            "fresh_since_fire": np.asarray(self.fresh_since_fire, bool),
            "n_uploads": int(self.n_uploads),
            "n_triggers": int(self.n_triggers),
            "bytes_up": np.asarray(self.bytes_up, float),
            "bytes_down": np.asarray(self.bytes_down, float),
        }

    def load_state_dict(self, state: Optional[dict]) -> None:
        """Restore ``state_dict`` output; ``None`` (a legacy checkpoint
        with no bus section) resets every counter to the fresh-bus zeros
        — the documented legacy behaviour, never garbage."""
        n = self.fed.n_clients
        if state is None:
            self.last_upload_t = np.full(n, -np.inf)
            self.uploads_since_fire = 0
            self.fresh_since_fire = np.zeros(n, bool)
            self.n_uploads = 0
            self.n_triggers = 0
            self.bytes_up = np.zeros(n)
            self.bytes_down = np.zeros(n)
            return
        # np.array (copy): np.asarray of a restored jnp buffer is a
        # READ-ONLY view, and these counters are mutated in place
        self.last_upload_t = np.array(state["last_upload_t"], float)
        self.uploads_since_fire = int(state["uploads_since_fire"])
        self.fresh_since_fire = np.array(state["fresh_since_fire"], bool)
        self.n_uploads = int(state["n_uploads"])
        self.n_triggers = int(state["n_triggers"])
        self.bytes_up = np.array(state["bytes_up"], float)
        self.bytes_down = np.array(state["bytes_down"], float)
