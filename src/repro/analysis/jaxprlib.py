"""Jaxpr-walking machinery for the ``jaxpr`` rule family.

Three analyses over a ``ClosedJaxpr`` (all recursion-aware — entry points
jit their bodies, so the interesting equations sit inside nested ``jit``
calls):

  * ``key_consumption`` / ``key_reuse_events`` — global value numbering
    of PRNG keys: the same key value consumed by two random draws (or a
    draw plus a split/fold_in) means overlapping random streams.
  * ``output_dependencies`` — per-OUTPUT set of input positions each
    output depends on, with PRECISE propagation through transparent call
    primitives (jit/remat/custom_jvp). Precision matters: a
    conservative union-through-calls would claim every output depends on
    every input and the masked-update auditor could never catch a mutant.
  * ``find_downcasts`` / ``random_draw_shapes`` — flat scans for
    ``convert_element_type`` precision drops and ``random_bits`` draw
    shapes.

Control-flow bodies (scan/while/cond) are handled conservatively: their
sub-jaxprs are walked for consumption/downcast/draw events with fresh
value identities, and dependence treats them as opaque (every output
depends on every input). None of the audited entry points put the
interesting logic inside control flow today; the conservatism is
documented here so a future auditor knows where precision ends.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, Iterator, List, Optional, Set, Tuple

import jax
import jax.extend.core as jcore
import jax.numpy as jnp
from jax._src.core import DropVar  # the one jaxpr class jax.extend omits

# primitives that CONSUME key randomness (drawing values) vs DERIVE fresh
# keys. fold_in/split are listed as consumers too: reusing one key for a
# draw AND a derivation overlaps the derived stream with the drawn one.
DRAW_PRIMS = frozenset({"random_bits"})
DERIVE_PRIMS = frozenset({"random_split", "random_fold_in"})

# call primitives whose sub-jaxpr invars/outvars map POSITIONALLY to the
# equation's invars/outvars — safe to recurse through precisely
_TRANSPARENT_CALLS = frozenset({
    "jit", "closed_call", "remat2", "custom_jvp_call", "custom_vjp_call",
})


def _as_open(j) -> Optional[jcore.Jaxpr]:
    if isinstance(j, jcore.ClosedJaxpr):
        return j.jaxpr
    if isinstance(j, jcore.Jaxpr):
        return j
    return None


def _transparent_sub(eqn) -> Optional[jcore.Jaxpr]:
    """The positionally-mapped sub-jaxpr of a transparent call eqn."""
    if eqn.primitive.name not in _TRANSPARENT_CALLS:
        return None
    sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
    sub = _as_open(sub)
    if sub is None or len(sub.invars) != len(eqn.invars) or \
            len(sub.outvars) != len(eqn.outvars):
        return None     # nonstandard binding: treat as opaque
    return sub


def _opaque_subs(eqn) -> List[jcore.Jaxpr]:
    """Every sub-jaxpr of a non-transparent eqn (scan/while/cond bodies),
    walked with fresh identities."""
    subs: List[jcore.Jaxpr] = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for item in vs:
            j = _as_open(item)
            if j is not None:
                subs.append(j)
    return subs


def iter_all_eqns(closed) -> Iterator[jcore.JaxprEqn]:
    """Every equation, recursing through every nested sub-jaxpr."""
    stack = [_as_open(closed)]
    while stack:
        j = stack.pop()
        if j is None:
            continue
        for eqn in j.eqns:
            yield eqn
            sub = _transparent_sub(eqn)
            if sub is not None:
                stack.append(sub)
            else:
                stack.extend(_opaque_subs(eqn))


# --------------------------------------------------------------------------
# PRNG key consumption (global value numbering)
# --------------------------------------------------------------------------

def _is_key_aval(aval) -> bool:
    try:
        return jnp.issubdtype(aval.dtype, jax.dtypes.prng_key)
    except (AttributeError, TypeError):
        return False


@dataclasses.dataclass(frozen=True)
class KeyEvent:
    """One consumption of a key value by a random primitive."""
    value_id: int
    prim: str            # the consuming primitive's name
    eqn_str: str         # rendered equation, for the report


def key_consumption(closed) -> List[KeyEvent]:
    """All key-consumption events, with value ids that are stable across
    transparent call boundaries (a key passed into a jitted body is the
    SAME value inside it)."""
    events: List[KeyEvent] = []
    counter = itertools.count()

    def walk(jaxpr: jcore.Jaxpr, env: Dict[jcore.Var, int]) -> None:
        def vid(v) -> int:
            if isinstance(v, jcore.Literal):
                return next(counter)
            if v not in env:
                env[v] = next(counter)
            return env[v]

        for cv in jaxpr.constvars:
            env.setdefault(cv, next(counter))
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in DRAW_PRIMS or name in DERIVE_PRIMS:
                for v in eqn.invars:
                    if not isinstance(v, jcore.Literal) and \
                            _is_key_aval(v.aval):
                        events.append(KeyEvent(vid(v), name, str(eqn)))
            sub = _transparent_sub(eqn)
            if sub is not None:
                inner: Dict[jcore.Var, int] = {
                    iv: vid(ov) for iv, ov in zip(sub.invars, eqn.invars)}
                walk(sub, inner)
                for ov, sv in zip(eqn.outvars, sub.outvars):
                    if not isinstance(sv, jcore.Literal) and \
                            not isinstance(ov, DropVar):
                        env[ov] = inner.get(sv, next(counter))
                continue
            for j in _opaque_subs(eqn):
                walk(j, {})
            for ov in eqn.outvars:
                if not isinstance(ov, DropVar):
                    env[ov] = next(counter)

    walk(_as_open(closed), {})
    return events


def key_reuse_events(closed) -> List[Tuple[int, List[KeyEvent]]]:
    """Key values whose consumption pattern overlaps random streams:
    >= 2 draws from one key, or a draw plus a split/fold_in of the same
    key. Repeated splits alone are NOT flagged (deterministic and
    stream-disjoint, merely redundant)."""
    by_id: Dict[int, List[KeyEvent]] = {}
    for ev in key_consumption(closed):
        by_id.setdefault(ev.value_id, []).append(ev)
    bad = []
    for vid, evs in sorted(by_id.items()):
        draws = sum(1 for e in evs if e.prim in DRAW_PRIMS)
        derives = sum(1 for e in evs if e.prim in DERIVE_PRIMS)
        if draws >= 2 or (draws >= 1 and derives >= 1):
            bad.append((vid, evs))
    return bad


# --------------------------------------------------------------------------
# per-output input dependence
# --------------------------------------------------------------------------

def _jaxpr_out_deps(jaxpr: jcore.Jaxpr,
                    memo: Dict[int, List[Set[int]]]) -> List[Set[int]]:
    """For each output of ``jaxpr``: the set of ITS invar positions the
    output depends on. Memoized by jaxpr identity — jitted helpers show
    up many times under vmap."""
    cached = memo.get(id(jaxpr))
    if cached is not None:
        return cached
    deps: Dict[jcore.Var, Set[int]] = {
        v: {i} for i, v in enumerate(jaxpr.invars)}
    for cv in jaxpr.constvars:
        deps[cv] = set()

    def var_deps(v) -> Set[int]:
        if isinstance(v, jcore.Literal):
            return set()
        return deps.get(v, set())

    for eqn in jaxpr.eqns:
        in_deps = [var_deps(v) for v in eqn.invars]
        sub = _transparent_sub(eqn)
        if sub is not None:
            sub_deps = _jaxpr_out_deps(sub, memo)
            for ov, sd in zip(eqn.outvars, sub_deps):
                if not isinstance(ov, DropVar):
                    deps[ov] = set().union(*(in_deps[p] for p in sd)) \
                        if sd else set()
        else:
            # opaque (incl. scan/while/cond): every output <- every input
            union: Set[int] = set().union(*in_deps) if in_deps else set()
            for ov in eqn.outvars:
                if not isinstance(ov, DropVar):
                    deps[ov] = union
    out = [var_deps(v) for v in jaxpr.outvars]
    memo[id(jaxpr)] = out
    return out


def output_dependencies(closed) -> List[Set[int]]:
    """Per flattened output: which flattened-input positions it depends
    on, precise through transparent calls (see module docstring)."""
    return _jaxpr_out_deps(_as_open(closed), {})


# --------------------------------------------------------------------------
# flat scans
# --------------------------------------------------------------------------

_LOW_FLOATS = (jnp.bfloat16, jnp.float16)
_TINY_INTS = (jnp.int8, jnp.uint8)


@dataclasses.dataclass(frozen=True)
class Downcast:
    src: str
    dst: str
    eqn_str: str


def find_downcasts(closed) -> List[Downcast]:
    """``convert_element_type`` equations that drop precision: fp32/fp64
    to bf16/f16, or any float to int8/uint8 (quantization). Legal only
    inside the wire-codec boundary — the caller decides which entry
    points get that exemption."""
    out: List[Downcast] = []
    for eqn in iter_all_eqns(closed):
        if eqn.primitive.name != "convert_element_type":
            continue
        try:
            src = jnp.dtype(eqn.invars[0].aval.dtype)
        except TypeError:
            continue    # extended dtype (PRNG key) — not a numeric cast
        dst = jnp.dtype(eqn.params["new_dtype"])
        drop = (src in (jnp.dtype(jnp.float32), jnp.dtype(jnp.float64))
                and dst in tuple(jnp.dtype(t) for t in _LOW_FLOATS))
        quant = (jnp.issubdtype(src, jnp.floating)
                 and dst in tuple(jnp.dtype(t) for t in _TINY_INTS))
        if drop or quant:
            out.append(Downcast(str(src), str(dst), str(eqn)))
    return out


def random_draw_shapes(closed) -> List[Tuple[Tuple[int, ...], str]]:
    """The requested shape of every ``random_bits`` draw (threefry output
    values depend on this shape — the PR 5 padded-draw bug class)."""
    out = []
    for eqn in iter_all_eqns(closed):
        if eqn.primitive.name in DRAW_PRIMS:
            shape = tuple(int(d) for d in eqn.params.get("shape", ()))
            out.append((shape, str(eqn)))
    return out
