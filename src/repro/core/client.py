"""Client-side state and the vmapped cohort step (Algorithm 1 line 12).

Clients of the same architecture family form a *cohort*: their params are a
stacked pytree advanced with one vmapped jit'd step. Heterogeneity across
cohorts is total (different architectures, layer counts, widths) — only
messengers ever cross cohort boundaries, exactly the paper's constraint.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distill import local_loss, ref_loss
from repro.core.messenger import cohort_messengers
from repro.optim import Optimizer

Params = Any


@dataclasses.dataclass
class Cohort:
    """All clients sharing one model family.

    Under device sharding (``repro.sharding.place_cohort_stacks``) the
    stacked arrays carry ``n_pad`` extra GHOST rows so the client axis
    divides the mesh — ghosts replicate the last real client and are
    permanently frozen by the step's trainable mask. ``client_ids`` always
    lists REAL clients only."""
    family_name: str
    apply_fn: Callable[[Params, jnp.ndarray], jnp.ndarray]
    params: Params                       # stacked (n_c + n_pad, ...)
    opt_state: Any                       # stacked
    client_ids: np.ndarray               # (n_c,) global client indices
    data: Dict[str, jnp.ndarray]         # {x (n_c+n_pad,M,L), y (..,M)}
    n_pad: int = 0                       # ghost rows (device-multiple pad)
    sharding: Any = None                 # NamedSharding of the stacks
    optimizer: Optional[Optimizer] = None   # per-family optimizer; None
    # falls back to the federation-wide default (legacy cohorts)

    @property
    def n_clients(self) -> int:
        return len(self.client_ids)

    @property
    def n_rows(self) -> int:
        """Stacked rows including ghost padding."""
        return self.n_clients + self.n_pad

    @property
    def padded_ids(self) -> np.ndarray:
        """Global client index per stacked row; ghost rows alias the last
        real client (their targets/availability gather somewhere valid —
        the trainable mask is what actually silences them)."""
        if self.n_pad == 0:
            return self.client_ids
        return np.concatenate(
            [self.client_ids,
             np.full(self.n_pad, self.client_ids[-1],
                     self.client_ids.dtype)])

    @property
    def real_params(self) -> Params:
        """Params of the real clients only (ghost rows sliced off)."""
        if self.n_pad == 0:
            return self.params
        return jax.tree.map(lambda a: a[: self.n_clients], self.params)

    @property
    def real_opt_state(self) -> Any:
        if self.n_pad == 0:
            return self.opt_state
        return jax.tree.map(lambda a: a[: self.n_clients], self.opt_state)

    @property
    def has_experts(self) -> bool:
        """Whether the family has expert layers (its apply_fn is a
        ``repro.models.zoo.ExpertFamilyApply``): its cohort step, the
        donating ``expert_cohort_step``, also returns token-choice
        counts."""
        return hasattr(self.apply_fn, "with_stats")

    def step_args(self, rows: int) -> Dict[str, int]:
        """Args of the ``repro.cohort_step`` span of a step over ``rows``
        samples a client: for a family with expert layers, the experts
        each layer holds and the tokens stepped; else none."""
        if not self.has_experts:
            return {}
        return {"experts_held": self.apply_fn.experts_held,
                "tokens": self.n_clients * rows * self.apply_fn.seq_len}


def make_cohort(family_name: str, init_fn, apply_fn, optimizer: Optimizer,
                client_ids, data, key) -> Cohort:
    keys = jax.random.split(key, len(client_ids))
    params = jax.vmap(init_fn)(keys)
    opt_state = jax.vmap(optimizer.init)(params)
    return Cohort(family_name, apply_fn, params, opt_state,
                  np.asarray(client_ids), data, optimizer=optimizer)


def _client_loss(apply_fn, params, x, y, ref_x, targets, rho: float,
                 use_ref: bool):
    loc = local_loss(apply_fn, params, x, y)
    if not use_ref:
        return loc
    ref = ref_loss(apply_fn, params, ref_x, targets)
    return (1.0 - rho) * loc + rho * ref


def _gated_update(optimizer: Optimizer, p, s, grads, on):
    """(params, opt_state) after one optimizer step, or unchanged where
    ``on`` is False."""
    updates, new_s = optimizer.update(grads, s, p)
    gate = on.astype(jnp.float32)
    new_p = jax.tree.map(
        lambda a, u: (a + gate * u.astype(a.dtype)).astype(a.dtype),
        p, updates)
    # freeze optimizer state too when inactive: gate EVERY leaf by
    # broadcasting the scalar mask — a shape-conditional gate would let
    # mismatched leaves (e.g. scalar step counters) silently advance,
    # and a woken client would resume with wrong Adam bias correction
    new_s = jax.tree.map(lambda a, b: jnp.where(on, b, a), s, new_s)
    return new_p, new_s


def _expert_grads(apply_fn, params, x, y, ref_x, targets, rho: float,
                  use_ref: bool):
    """(loss, counts, grads) of Eq. 6 (Eq. 3 alone without the reference
    term) for a family with expert layers: ``counts`` sums its forwards'
    token-choice counts (``apply_fn.with_stats``). The reference term is
    differentiated over equal blocks of at most ``apply_fn.ref_block``
    samples, one after another, and the gradients added up: the same
    sum, holding one block's activations at a time."""

    def value_and_grad(loss_of):
        def f(q):
            counts = []

            def fn(p, xs):
                logits, c = apply_fn.with_stats(p, xs)
                counts.append(c)
                return logits
            return loss_of(fn, q), sum(counts)
        (v, c), g = jax.value_and_grad(f, has_aux=True)(params)
        return v, c, g

    block = apply_fn.ref_block
    r = ref_x.shape[0]
    if not (use_ref and block and block < r):
        return value_and_grad(lambda fn, q: _client_loss(
            fn, q, x, y, ref_x, targets, rho, use_ref))
    nb = next(n for n in range(-(-r // block), r + 1) if r % n == 0)
    acc = value_and_grad(lambda fn, q: (1.0 - rho) * local_loss(fn, q, x, y))

    def one_block(acc, blk):
        rx, rt = blk
        part = value_and_grad(lambda fn, q: rho / nb * ref_loss(fn, q, rx, rt))
        return jax.tree.map(jnp.add, acc, part), None

    acc, _ = jax.lax.scan(one_block, acc,
                          (ref_x.reshape(nb, r // nb, *ref_x.shape[1:]),
                           targets.reshape(nb, r // nb, *targets.shape[1:])))
    return acc


def _cohort_step(apply_fn, optimizer: Optimizer, params, opt_state,
                 batch_x, batch_y, ref_x, targets, trainable,
                 rho: float, use_ref: bool):
    """One vmapped SGD step for a whole cohort (jit'd as ``cohort_step``;
    ``sharded_cohort_step`` jits the same body pinned to a client mesh).

    batch_x (n_c,B,L), batch_y (n_c,B), targets (n_c,R,C) per-client
    distill targets, trainable (n_c,) bool (inactive clients frozen).
    Returns (params, opt_state, per-client loss); for a family with
    expert layers (``Cohort.has_experts``; gradients by
    ``_expert_grads``) also each client's token choices per expert layer
    and held expert (n_c, n_expert_layers, held)."""
    experts = hasattr(apply_fn, "with_stats")

    def one(p, s, x, y, t, on):
        if experts:
            loss, counts, grads = _expert_grads(apply_fn, p, x, y, ref_x,
                                                t, rho, use_ref)
            return (*_gated_update(optimizer, p, s, grads, on), loss,
                    counts)
        loss, grads = jax.value_and_grad(
            lambda q: _client_loss(apply_fn, q, x, y, ref_x, t, rho,
                                   use_ref))(p)
        return (*_gated_update(optimizer, p, s, grads, on), loss)

    return jax.vmap(one)(params, opt_state, batch_x, batch_y, targets,
                         trainable)


_STEP_STATICS = ("apply_fn", "optimizer", "rho", "use_ref")
cohort_step = jax.jit(_cohort_step, static_argnames=_STEP_STATICS)
# the step of a family with expert layers: it updates params and
# optimizer state in place, since weights and Adam state that fill a
# chip cannot be held twice
expert_cohort_step = jax.jit(_cohort_step, static_argnames=_STEP_STATICS,
                             donate_argnames=("params", "opt_state"))


def _cohort_messenger_upload(apply_fn, params, ref_x, codec=None):
    """(n_c, R, C) log-prob messengers for the cohort.

    ``codec`` (a hashable ``wire.Codec``, static under jit) encodes the
    stack ON the client: the forward pass and the wire encode fuse into
    one compiled call and the return value is the Payload that actually
    crosses the device boundary. ``None`` keeps the raw-array form."""
    return cohort_messengers(apply_fn, params, ref_x, codec=codec)


cohort_messenger_upload = jax.jit(_cohort_messenger_upload,
                                  static_argnames=("apply_fn", "codec"))


@functools.lru_cache(maxsize=None)
def sharded_cohort_step(mesh):
    """``cohort_step`` pinned to a client mesh: the vmapped rows never
    interact, so pinning every output to the mesh's client axis
    (out_shardings broadcast over the pytree) partitions the whole step
    with zero collectives — params/opt state stay resident on their
    shard across steps. Cached per mesh so each cohort shape compiles
    once. Inputs must be padded to a device multiple
    (``repro.sharding.place_cohort_stacks``)."""
    from repro.sharding import client_sharding
    return jax.jit(_cohort_step, static_argnames=_STEP_STATICS,
                   out_shardings=client_sharding(mesh))


@functools.lru_cache(maxsize=None)
def sharded_messenger_upload(mesh):
    """``cohort_messenger_upload`` pinned to a client mesh: every Payload
    field has a leading client axis, so one row sharding broadcasts over
    the whole encoded pytree."""
    from repro.sharding import client_sharding
    return jax.jit(_cohort_messenger_upload,
                   static_argnames=("apply_fn", "codec"),
                   out_shardings=client_sharding(mesh))


@functools.partial(jax.jit, static_argnames=("apply_fn",))
def cohort_accuracy(apply_fn, params, xs, ys):
    """Per-client accuracy on stacked eval shards (n_c, M, L)/(n_c, M)."""

    def one(p, x, y):
        logits = apply_fn(p, x)
        return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))

    return jax.vmap(one)(params, xs, ys)


@functools.partial(jax.jit, static_argnames=("apply_fn",))
def cohort_accuracy_masked(apply_fn, params, xs, ys, mask):
    """Per-client accuracy over UNEQUAL shard lengths: shards are padded
    to the cohort max and ``mask (n_c, M)`` marks the real samples, so no
    client's tail is truncated to the shortest shard."""

    def one(p, x, y, m):
        logits = apply_fn(p, x)
        hit = (jnp.argmax(logits, -1) == y) & m
        return hit.sum() / jnp.maximum(m.sum(), 1).astype(jnp.float32)

    return jax.vmap(one)(params, xs, ys, mask)


@functools.partial(jax.jit, static_argnames=("apply_fn",))
def cohort_pred(apply_fn, params, xs):
    return jax.vmap(lambda p, x: jnp.argmax(apply_fn(p, x), -1))(params, xs)
