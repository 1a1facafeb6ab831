"""The jaxpr cost interpreter: FLOPs, HBM traffic, and peak residency.

The interpreter flattens a ``ClosedJaxpr`` into a linear program of
*buffers* and *ops* (recursing through transparent calls with the same
positional mapping ``jaxprlib`` uses, so a value passed into a jitted
body keeps one buffer identity) and then runs three analyses:

  * **FLOPs** — a per-primitive cost model: ``dot_general`` pays
    ``2 * out_elems * contracted``, reductions pay their input element
    count, transcendentals pay a fixed multiple of their output count,
    data-movement primitives pay zero.
  * **bytes** — an HBM-traffic model in the spirit of
    ``launch/hlo_cost``: only MATERIALIZED buffers are read or written.
    An elementwise producer whose single consumer is another fusible op
    never materializes (XLA fuses the chain), so ``1/max(div, eps)``
    costs one read of ``div`` and one write of the result, not four
    (N,N) round trips. Scatter-family ops alias their first operand
    (XLA updates in place) and pay traffic for the touched region only.
  * **peak residency** — linear-scan liveness over the flattened op
    list. ``peak_bytes`` counts everything live at once (arguments
    included); ``temp_bytes`` counts only intermediate allocations —
    buffers that are neither inputs, nor aliased onto inputs, nor the
    jaxpr's outputs. ``temp_bytes`` is the metric the
    ``superlinear-memory`` rule fits: the delta graph path *updates* an
    (N,N) cache it was handed, but must never *allocate* Θ(N²) afresh.

Control flow is handled conservatively: ``scan`` bodies multiply
flops/bytes by the trip count (``length``) and contribute their
temporaries once; ``while``/``cond`` bodies count once. None of the
audited entry points hide hot loops inside control flow today.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax.extend.core as jcore

from repro.analysis.jaxprlib import (DropVar, _as_open, _opaque_subs,
                                     _transparent_sub)

# --------------------------------------------------------------------------
# per-primitive FLOP model
# --------------------------------------------------------------------------

# transcendental / special-function primitives: several hardware ops per
# element (polynomial approximations); the exact multiple is a model
# constant, not a measurement
TRANSCENDENTAL_WEIGHT = 4
_TRANSCENDENTALS = frozenset({
    "exp", "exp2", "expm1", "log", "log2", "log1p", "tanh", "logistic",
    "erf", "erfc", "erf_inv", "sin", "cos", "tan", "asin", "acos", "atan",
    "atan2", "sinh", "cosh", "asinh", "acosh", "atanh", "pow", "rsqrt",
    "sqrt", "cbrt", "digamma", "lgamma",
})

# pure data movement / bookkeeping: zero flops
_MOVEMENT = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "squeeze", "slice",
    "concatenate", "pad", "gather", "dynamic_slice", "dynamic_update_slice",
    "scatter", "scatter-add", "scatter_add", "scatter_mul", "scatter_min",
    "scatter_max", "convert_element_type", "iota", "copy", "device_put",
    "rev", "select_n", "stop_gradient", "split", "expand_dims",
})

_REDUCTIONS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin", "cumsum", "cumprod",
    "cummax", "cummin", "cumlogsumexp", "top_k", "reduce_window_sum",
    "reduce_window_max",
})

# primitives XLA fuses into elementwise chains: a single-consumer output
# of one of these feeding another fusible op (or a reduction) stays in
# registers and never touches HBM
_FUSIBLE = _TRANSCENDENTALS | frozenset({
    "add", "sub", "mul", "div", "rem", "max", "min", "neg", "abs", "sign",
    "floor", "ceil", "round", "is_finite", "not", "and", "or", "xor",
    "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "lt", "le", "gt", "ge", "eq", "ne", "select_n", "clamp", "nextafter",
    "integer_pow", "square", "reciprocal", "broadcast_in_dim", "iota",
    "convert_element_type", "reshape", "squeeze", "expand_dims", "copy",
})
# valid fusion *consumers* additionally include reductions (input fusion)
_FUSION_CONSUMERS = _FUSIBLE | _REDUCTIONS

# free-regeneration ops: XLA duplicates these into EVERY consumer fusion
# (multi-consumer included), so their product only materializes if it
# escapes as a jaxpr output — the blowup rule can therefore only catch a
# broadcast that is actually returned, which is exactly the case that
# costs real HBM
_REGENERABLE = frozenset({"broadcast_in_dim", "iota"})

# ops that update their first operand in place (output aliases it); the
# traffic they pay is the touched region, not the whole array
_INPLACE = frozenset({
    "scatter", "scatter-add", "scatter_add", "scatter_mul", "scatter_min",
    "scatter_max", "dynamic_update_slice",
})
_ALIAS_ONLY = frozenset({"device_put", "copy"})


def aval_nbytes(aval) -> int:
    """Bytes of one buffer holding ``aval`` (extended dtypes — PRNG keys —
    are charged their key-data width)."""
    size = int(getattr(aval, "size", 1))
    try:
        item = int(aval.dtype.itemsize)
    except (AttributeError, TypeError):
        item = 8      # threefry key payload: 2 x uint32
    return size * item


def _numel(aval) -> int:
    return int(getattr(aval, "size", 1))


def eqn_flops(eqn) -> float:
    """The per-primitive FLOP model (see module docstring)."""
    name = eqn.primitive.name
    out_elems = sum(_numel(v.aval) for v in eqn.outvars
                    if not isinstance(v, DropVar))
    in_elems = sum(_numel(v.aval) for v in eqn.invars)
    if name == "dot_general":
        (lhs_c, _), _ = eqn.params["dimension_numbers"]
        lhs_shape = eqn.invars[0].aval.shape
        contracted = 1
        for d in lhs_c:
            contracted *= int(lhs_shape[d])
        return 2.0 * out_elems * contracted
    if name == "conv_general_dilated":
        rhs_shape = eqn.invars[1].aval.shape
        spatial = 1
        for d in rhs_shape[2:]:
            spatial *= int(d)
        cin = int(rhs_shape[1]) if len(rhs_shape) > 1 else 1
        return 2.0 * out_elems * spatial * cin
    if name in _MOVEMENT:
        return 0.0
    if name == "sort":
        return float(in_elems) * max(1.0, math.log2(max(in_elems, 2)))
    if name in _REDUCTIONS:
        return float(in_elems)
    if name in _TRANSCENDENTALS:
        return float(TRANSCENDENTAL_WEIGHT * out_elems)
    if name == "random_bits":
        return 16.0 * out_elems       # threefry rounds, integer ops
    # default: one op per output element (add/mul/compare/...)
    return float(out_elems)


# --------------------------------------------------------------------------
# flattening
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Buffer:
    bid: int
    nbytes: int
    kind: str                  # "invar" | "const" | "eqn"


@dataclasses.dataclass
class FlatOp:
    prim: str
    in_bufs: List[int]
    out_bufs: List[int]
    alloc: List[bool]          # per out buffer: freshly allocated here?
    mult: float                # execution multiplier (scan trip counts)
    flops: float               # UNSCALED flops of one execution
    eqn_repr: str
    out_nbytes: int
    in_nbytes: int             # sum of input buffer bytes (aliased incl.)
    inplace: bool


@dataclasses.dataclass
class Program:
    buffers: Dict[int, Buffer] = dataclasses.field(default_factory=dict)
    ops: List[FlatOp] = dataclasses.field(default_factory=list)
    invar_bufs: List[int] = dataclasses.field(default_factory=list)
    outvar_bufs: List[int] = dataclasses.field(default_factory=list)


def flatten(closed) -> Program:
    """Linearize ``closed`` into buffers + ops with global buffer ids."""
    prog = Program()
    counter = [0]

    def new_buf(aval, kind: str) -> int:
        counter[0] += 1
        b = Buffer(counter[0], aval_nbytes(aval), kind)
        prog.buffers[b.bid] = b
        return b.bid

    def buf_of(v, env) -> int:
        if isinstance(v, jcore.Literal):
            return new_buf(v.aval, "const")
        if v not in env:                     # e.g. unflagged constvar
            env[v] = new_buf(v.aval, "const")
        return env[v]

    def walk(jaxpr: jcore.Jaxpr, env, mult: float) -> None:
        for cv in jaxpr.constvars:
            env.setdefault(cv, new_buf(cv.aval, "const"))
        for eqn in jaxpr.eqns:
            sub = _transparent_sub(eqn)
            if sub is not None:
                inner = {iv: buf_of(ov, env)
                         for iv, ov in zip(sub.invars, eqn.invars)}
                walk(sub, inner, mult)
                for ov, sv in zip(eqn.outvars, sub.outvars):
                    if not isinstance(ov, DropVar):
                        env[ov] = buf_of(sv, inner)
                continue
            name = eqn.primitive.name
            m = mult
            if name == "scan":
                m = mult * float(eqn.params.get("length", 1))
            if name in ("scan", "while", "cond"):
                for j in _opaque_subs(eqn):
                    walk(j, {}, m)
            in_bufs = [buf_of(v, env) for v in eqn.invars]
            in_nbytes = sum(prog.buffers[b].nbytes for b in in_bufs)
            outs = [v for v in eqn.invars[:0]]  # placeholder, replaced below
            out_bufs: List[int] = []
            alloc: List[bool] = []
            inplace = (name in _INPLACE or name in _ALIAS_ONLY) and bool(
                eqn.invars) and not isinstance(eqn.outvars[0], DropVar)
            if inplace:
                # output 0 must match operand 0's width to alias it
                o0 = eqn.outvars[0].aval
                i0 = eqn.invars[0].aval
                inplace = aval_nbytes(o0) == aval_nbytes(i0)
            for i, ov in enumerate(eqn.outvars):
                if isinstance(ov, DropVar):
                    out_bufs.append(new_buf(ov.aval, "eqn"))
                    alloc.append(True)
                    continue
                if i == 0 and inplace:
                    env[ov] = in_bufs[0]
                    out_bufs.append(in_bufs[0])
                    alloc.append(False)
                else:
                    env[ov] = new_buf(ov.aval, "eqn")
                    out_bufs.append(env[ov])
                    alloc.append(True)
            del outs
            out_nbytes = sum(aval_nbytes(ov.aval) for ov in eqn.outvars)
            prog.ops.append(FlatOp(
                prim=name, in_bufs=in_bufs, out_bufs=out_bufs, alloc=alloc,
                mult=m if name in ("scan", "while", "cond") else mult,
                flops=eqn_flops(eqn), eqn_repr=str(eqn),
                out_nbytes=out_nbytes, in_nbytes=in_nbytes,
                inplace=inplace))

    jaxpr = _as_open(closed)
    env: Dict[jcore.Var, int] = {}
    for v in jaxpr.invars:
        env[v] = new_buf(v.aval, "invar")
        prog.invar_bufs.append(env[v])
    walk(jaxpr, env, 1.0)
    for v in jaxpr.outvars:
        prog.outvar_bufs.append(buf_of(v, env))
    return prog


# --------------------------------------------------------------------------
# materialization (fusion model) + the three analyses
# --------------------------------------------------------------------------

def materialized_mask(prog: Program) -> Dict[int, bool]:
    """Buffer id -> does it ever hit HBM? Invars, consts, outvars, and
    multi-consumer or fusion-breaking products materialize; an
    elementwise product with exactly one fusible consumer stays in
    registers (see module docstring)."""
    consumers: Dict[int, List[int]] = {}
    producer: Dict[int, int] = {}
    for i, op in enumerate(prog.ops):
        for b in op.in_bufs:
            consumers.setdefault(b, []).append(i)
        for b, fresh in zip(op.out_bufs, op.alloc):
            if fresh:
                producer[b] = i
    out_set = set(prog.outvar_bufs)
    mat: Dict[int, bool] = {}
    for bid, buf in prog.buffers.items():
        if buf.kind in ("invar", "const") or bid in out_set:
            mat[bid] = True
            continue
        pi = producer.get(bid)
        if pi is None:
            mat[bid] = True
            continue
        op = prog.ops[pi]
        if op.prim in _REGENERABLE:
            mat[bid] = False
            continue
        cons = consumers.get(bid, [])
        fusible_chain = (
            op.prim in _FUSIBLE
            and len(op.out_bufs) == 1
            and len(cons) == 1
            and prog.ops[cons[0]].prim in _FUSION_CONSUMERS)
        mat[bid] = not fusible_chain
    return mat


@dataclasses.dataclass
class CostSummary:
    """One entry point's static cost (model units, not measurements)."""
    flops: float = 0.0
    bytes: float = 0.0             # modeled HBM traffic, read + write
    peak_bytes: float = 0.0        # max live incl. arguments + outputs
    temp_bytes: float = 0.0        # max live INTERMEDIATE allocations
    arg_bytes: float = 0.0
    out_bytes: float = 0.0
    flops_by_prim: Dict[str, float] = dataclasses.field(default_factory=dict)
    n_eqns: int = 0

    @property
    def intensity(self) -> float:
        """Arithmetic intensity against pure argument+result traffic —
        the roofline x-axis for a perfectly-fused kernel."""
        io = self.arg_bytes + self.out_bytes
        return self.flops / io if io else 0.0

    def as_dict(self) -> dict:
        return {"flops": self.flops, "bytes": self.bytes,
                "peak_bytes": self.peak_bytes, "temp_bytes": self.temp_bytes,
                "arg_bytes": self.arg_bytes, "out_bytes": self.out_bytes,
                "n_eqns": self.n_eqns}


def _op_traffic(op: FlatOp, prog: Program, mat: Dict[int, bool]) -> float:
    """Modeled HBM bytes of one execution of ``op``."""
    if op.prim in _ALIAS_ONLY and op.inplace:
        return 0.0
    read = sum(prog.buffers[b].nbytes for b in set(op.in_bufs) if mat[b])
    if op.inplace:
        # in-place update: the aliased operand is not streamed in full;
        # the touched region ~ the update operand(s), written once
        touched = sum(prog.buffers[b].nbytes for b in set(op.in_bufs[1:])
                      if mat[b])
        read = touched
        write = touched
        return float(read + write)
    write = sum(prog.buffers[b].nbytes
                for b, fresh in zip(op.out_bufs, op.alloc)
                if fresh and mat[b])
    return float(read + write)


def summarize(closed) -> CostSummary:
    """Run the full cost interpretation of one traced entry point."""
    prog = flatten(closed)
    mat = materialized_mask(prog)
    s = CostSummary()
    s.arg_bytes = float(sum(prog.buffers[b].nbytes
                            for b in prog.invar_bufs))
    s.out_bytes = float(sum(prog.buffers[b].nbytes
                            for b in set(prog.outvar_bufs)))
    s.n_eqns = len(prog.ops)

    # flops + traffic (multiplier-scaled)
    for op in prog.ops:
        f = op.mult * op.flops
        s.flops += f
        if f:
            s.flops_by_prim[op.prim] = s.flops_by_prim.get(op.prim, 0.0) + f
        s.bytes += op.mult * _op_traffic(op, prog, mat)

    # linear-scan liveness (temporal; multipliers don't extend lifetimes)
    last_use: Dict[int, int] = {}
    for i, op in enumerate(prog.ops):
        for b in op.in_bufs:
            last_use[b] = i
        for b in op.out_bufs:
            last_use[b] = i
    end = len(prog.ops)
    for b in prog.outvar_bufs + prog.invar_bufs:
        last_use[b] = end                       # args/results pinned
    out_set = set(prog.outvar_bufs)

    live: Dict[int, Buffer] = {}
    for b in prog.invar_bufs:
        live[b] = prog.buffers[b]
    for bid, buf in prog.buffers.items():
        if buf.kind == "const":
            live[bid] = buf

    def tally() -> Tuple[float, float]:
        total = sum(b.nbytes for bid, b in live.items() if mat[bid])
        temp = sum(b.nbytes for bid, b in live.items()
                   if mat[bid] and b.kind == "eqn" and bid not in out_set)
        return float(total), float(temp)

    peak, temp_peak = tally()
    for i, op in enumerate(prog.ops):
        for b, fresh in zip(op.out_bufs, op.alloc):
            if fresh:
                live[b] = prog.buffers[b]
        t, tt = tally()
        peak = max(peak, t)
        temp_peak = max(temp_peak, tt)
        dead = [b for b in list(live) if last_use.get(b, -1) <= i]
        for b in dead:
            del live[b]
    s.peak_bytes = peak
    s.temp_bytes = temp_peak
    return s


# --------------------------------------------------------------------------
# blowup scan (the broadcast-blowup rule body)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Blowup:
    prim: str
    ratio: float
    out_nbytes: int
    eqn_str: str


def find_blowups(closed, ratio: float, floor_bytes: int,
                 allow_prims: Sequence[str] = ()) -> List[Blowup]:
    """Materialized eqn outputs more than ``ratio``x larger than all the
    eqn's inputs combined. Generative fills from scalars (every input
    <= 64 bytes) are exempt — ``jnp.zeros``/``iota`` initialization is
    how arrays are born, not a blowup; so are in-place updates and
    fusion-virtualized products that never touch HBM."""
    prog = flatten(closed)
    mat = materialized_mask(prog)
    out: List[Blowup] = []
    allow = frozenset(allow_prims)
    for op in prog.ops:
        if op.prim in allow or op.inplace:
            continue
        out_bytes = sum(prog.buffers[b].nbytes
                        for b, fresh in zip(op.out_bufs, op.alloc)
                        if fresh and mat[b])
        if out_bytes < floor_bytes:
            continue
        in_bytes = sum(prog.buffers[b].nbytes for b in set(op.in_bufs))
        if in_bytes <= 64:              # generative fill from scalars
            continue
        r = out_bytes / max(in_bytes, 1)
        if r > ratio:
            out.append(Blowup(op.prim, r, int(out_bytes),
                              op.eqn_repr[:200]))
    return out


# --------------------------------------------------------------------------
# scaling fits
# --------------------------------------------------------------------------

def fit_exponent(xs: Sequence[float], ys: Sequence[float]) -> float:
    """Least-squares slope of log(y) vs log(x): the leading exponent of a
    power law sampled at geometrically-spaced ``xs``."""
    if len(xs) != len(ys) or len(xs) < 2:
        raise ValueError(f"need >= 2 aligned samples, got {len(xs)} xs / "
                         f"{len(ys)} ys")
    lx = [math.log(float(x)) for x in xs]
    ly = [math.log(max(float(y), 1.0)) for y in ys]
    mx = sum(lx) / len(lx)
    my = sum(ly) / len(ly)
    num = sum((a - mx) * (b - my) for a, b in zip(lx, ly))
    den = sum((a - mx) ** 2 for a in lx)
    if den == 0:
        raise ValueError("scale samples must span at least two sizes")
    return num / den
