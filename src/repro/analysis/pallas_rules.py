"""Pallas auditors: every ``pallas_call`` grid must tile its operands.

The kernels pad inputs so each block shape divides the (padded) array
shape exactly — a mismatch silently reads garbage on TPU (or masks a
wrong ``index_map``). The rule intercepts ``pallas_call`` at the module
attribute every kernel imports (``from jax.experimental import pallas as
pl`` shares one module object), replays each kernel wrapper on odd probe
shapes in interpret mode, and validates every recorded invocation.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.registry import AnalysisContext, Violation, register_rule


@dataclasses.dataclass
class PallasCallRecord:
    """One intercepted ``pallas_call`` invocation: declared specs plus
    the ACTUAL operand shapes it was applied to."""
    kernel: str
    grid: Tuple[int, ...]
    in_blocks: List[Optional[Tuple[Optional[int], ...]]]
    out_blocks: List[Optional[Tuple[Optional[int], ...]]]
    in_shapes: List[Tuple[int, ...]]
    out_shapes: List[Tuple[int, ...]]


def _kernel_name(kernel) -> str:
    inner = getattr(kernel, "func", kernel)      # functools.partial
    return getattr(inner, "__qualname__",
                   getattr(inner, "__name__", repr(inner)))


def _block_shapes(specs) -> List[Optional[Tuple[Optional[int], ...]]]:
    if specs is None:
        return []
    specs = specs if isinstance(specs, (tuple, list)) else [specs]
    out = []
    for s in specs:
        bs = getattr(s, "block_shape", None)
        out.append(tuple(bs) if bs is not None else None)
    return out


def _out_shapes(out_shape) -> List[Tuple[int, ...]]:
    structs = out_shape if isinstance(out_shape, (tuple, list)) \
        else [out_shape]
    return [tuple(int(d) for d in s.shape) for s in structs]


@contextlib.contextmanager
def intercept_pallas_calls(records: List[PallasCallRecord]
                           ) -> Iterator[List[PallasCallRecord]]:
    """Swap ``pallas.pallas_call`` for a recording wrapper (restored on
    exit). Records are appended when the RETURNED callable runs — i.e.
    at kernel trace time, with the real operand shapes in hand."""
    import jax.experimental.pallas as plmod

    real = plmod.pallas_call

    def spy(kernel, *a, **kw):
        inner = real(kernel, *a, **kw)

        def wrapped(*arrays):
            grid = kw.get("grid", ())
            records.append(PallasCallRecord(
                kernel=_kernel_name(kernel),
                grid=tuple(grid) if isinstance(grid, (tuple, list))
                else (int(grid),),
                in_blocks=_block_shapes(kw.get("in_specs")),
                out_blocks=_block_shapes(kw.get("out_specs")),
                in_shapes=[tuple(int(d) for d in x.shape) for x in arrays],
                out_shapes=_out_shapes(kw.get("out_shape")),
            ))
            return inner(*arrays)

        return wrapped

    plmod.pallas_call = spy
    try:
        yield records
    finally:
        plmod.pallas_call = real


def check_record(rec: PallasCallRecord,
                 rule: str = "pallas-grid-divisibility") -> List[Violation]:
    """Every block dim must divide its operand dim exactly (``None``
    block entries mean 'whole dimension' and are exempt)."""
    out = []

    def check(kind: str, shapes, blocks) -> None:
        for i, (shape, block) in enumerate(zip(shapes, blocks)):
            if block is None:
                continue
            if len(block) != len(shape):
                out.append(Violation(
                    rule, f"{rec.kernel}#{kind}{i}",
                    f"block rank {len(block)} != operand rank "
                    f"{len(shape)} (block {block} vs shape {shape})"))
                continue
            for d, (s, b) in enumerate(zip(shape, block)):
                if b is None:
                    continue
                if int(s) % int(b):
                    out.append(Violation(
                        rule, f"{rec.kernel}#{kind}{i}d{d}",
                        f"operand dim {d} of size {s} is not divisible "
                        f"by block size {b} (grid {rec.grid}, block "
                        f"{block}) — pad the operand to a block multiple"))

    check("in", rec.in_shapes, rec.in_blocks)
    check("out", rec.out_shapes, rec.out_blocks)
    return out


# bumped per probe run so each run traces FRESH shapes: a jit-cache hit
# would skip the kernel body and the interception would record nothing
_PROBE_BUMP = itertools.count()


def run_kernel_probes() -> List[PallasCallRecord]:
    """Drive every kernel wrapper through odd probe shapes (interpret
    mode) under interception."""
    from repro.kernels import ops

    bump = 8 * next(_PROBE_BUMP)
    n, r, c = 9 + bump, 3, 7
    key = jax.random.key(13)
    logp = jax.nn.log_softmax(
        jax.random.normal(key, (n, r, c)) * 2.0, axis=-1)
    logp_b = logp[: 5 + bump]
    labels = jax.random.randint(jax.random.key(14), (r,), 0, c)
    w = jnp.ones((n, n), jnp.float32) / n
    nbrs = jax.random.randint(jax.random.key(16), (n, 3), 0, n)
    q = jax.random.randint(jax.random.key(15), (n, r, c),
                           0, 256).astype(jnp.uint8)
    scale = jnp.full((n, r), 0.05, jnp.float32)
    zp = jnp.zeros((n, r), jnp.float32)

    records: List[PallasCallRecord] = []
    with intercept_pallas_calls(records):
        ops.pairwise_kl(logp, backend="interpret")
        ops.pairwise_kl_pair(logp_b, logp, backend="interpret")
        ops.int8_pairwise_kl(q, scale, zp, backend="interpret")
        ops.soft_ce(logp, labels, backend="interpret")
        ops.neighbor_mean_dense(w, jnp.exp(logp), backend="interpret")
        ops.neighbor_mean(nbrs, w[:, :3], jnp.exp(logp), backend="interpret")
    if not records:
        raise RuntimeError(
            "pallas_call interception recorded nothing — kernel probes "
            "hit the jit cache; the probe shapes must be fresh per run")
    return records


@register_rule("pallas-grid-divisibility", family="pallas")
def pallas_grid_divisibility(ctx: AnalysisContext) -> Iterable[Violation]:
    """Replay every kernel wrapper on odd shapes and validate each
    recorded ``pallas_call``'s blocks against its operands."""
    for rec in run_kernel_probes():
        yield from check_record(rec)
