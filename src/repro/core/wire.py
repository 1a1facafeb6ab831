"""Messenger wire codecs — the encoded form messengers travel in.

SQMD's bandwidth story is that *only messengers* cross device
boundaries; this module gives that claim an actual wire format whose
size, round-trip error, and downstream graph fidelity are measurable.
A codec turns a stack of soft decisions ``(..., R, C)`` into a
``Payload`` (a pytree of wire-dtype arrays) and back:

    encode(x, domain) -> Payload        # what the client transmits
    decode(payload)   -> x_hat          # what the server reconstructs
    payload_bytes(payload) -> int       # what the link actually carried

Codecs are registered by name (``@register_codec``) and reachable from
``FederationConfig(uplink=..., downlink=...)`` and the ``federate``
CLI. The built-ins:

  dense32   fp32 pass-through — the bit-identical oracle (default)
  dense16   bf16 cast, 2x
  int8      per-row affine quantization: uint8 codes + per-row
            bf16 scale / zero-point (the row minimum), ~4x at C >= 32
  topk      top-k probabilities per reference sample + a renormalized
            tail mass (classic soft-label sparsification)

``domain`` records what the values are: messenger LOG-probabilities
(``"log"``, the uplink) or probability targets (``"prob"``, the
downlink K^n payloads). Lossy decodes renormalize in-domain so the
reconstruction is always a proper distribution; ``dense32`` never
touches the array at all.
"""
from __future__ import annotations

import abc
import dataclasses
from typing import Dict, Sequence, Tuple, Type, Union

import jax
import jax.numpy as jnp

_DOMAINS = ("log", "prob")
_PROB_FLOOR = 1e-10   # decode floor before a log: keeps KL terms finite


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Payload:
    """One encoded messenger batch: wire-dtype arrays + routing metadata.

    ``shape`` is the logical decoded shape ``(..., R, C)``; ``arrays``
    the codec-specific wire fields (stored at their WIRE dtypes, so
    ``payload_bytes`` is just their nbytes sum). Registered as a pytree
    so payloads flow through jit/vmap and the event queue unchanged."""
    codec: str
    domain: str
    shape: Tuple[int, ...]
    arrays: Dict[str, jnp.ndarray]

    @property
    def rows(self) -> int:
        """Number of messengers in the batch (product of leading dims)."""
        n = 1
        for d in self.shape[:-2]:
            n *= int(d)
        return n

    def tree_flatten(self):
        keys = tuple(sorted(self.arrays))
        return (tuple(self.arrays[k] for k in keys),
                (self.codec, self.domain, tuple(self.shape), keys))

    @classmethod
    def tree_unflatten(cls, aux, children):
        codec, domain, shape, keys = aux
        return cls(codec, domain, shape, dict(zip(keys, children)))


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

_CODECS: Dict[str, Type["Codec"]] = {}


def register_codec(name: str):
    """Class decorator: ``@register_codec("int8")`` binds ``cls.name`` and
    makes the codec reachable by name (config, CLI, checkpoints)."""

    def deco(cls: Type["Codec"]) -> Type["Codec"]:
        if name in _CODECS:
            raise ValueError(f"codec {name!r} already registered")
        cls.name = name
        _CODECS[name] = cls
        return cls

    return deco


def registered_codecs() -> Tuple[str, ...]:
    return tuple(sorted(_CODECS))


def get_codec(name: str) -> Type["Codec"]:
    try:
        return _CODECS[name]
    except KeyError:
        raise KeyError(f"unknown codec {name!r}; registered: "
                       f"{registered_codecs()}") from None


def as_codec(spec: Union[None, str, "Codec"]) -> "Codec":
    """Coerce None/name/instance into a Codec (None => dense32).

    Parameterized specs use ``name:arg`` — e.g. ``"topk:4"`` keeps the
    top 4 log-probs per reference sample."""
    if isinstance(spec, Codec):
        return spec
    if spec is None:
        return get_codec("dense32")()
    name, _, arg = spec.partition(":")
    return get_codec(name).from_arg(arg)


# --------------------------------------------------------------------------
# codec interface
# --------------------------------------------------------------------------

class Codec(abc.ABC):
    """A messenger wire format. Codecs are small frozen config holders —
    hashable, so ``encode`` can ride inside jit as a static argument."""

    name: str = "?"

    @classmethod
    def from_arg(cls, arg: str) -> "Codec":
        if arg:
            raise ValueError(f"codec {cls.name!r} takes no argument "
                             f"(got {arg!r})")
        return cls()

    @abc.abstractmethod
    def encode(self, x: jnp.ndarray, domain: str = "log") -> Payload:
        """``x (..., R, C)`` soft decisions -> wire Payload."""

    @abc.abstractmethod
    def decode(self, payload: Payload) -> jnp.ndarray:
        """Payload -> ``(..., R, C)`` fp32 reconstruction, renormalized
        in the payload's domain (except dense32: pure pass-through)."""

    def payload_bytes(self, payload: Payload) -> int:
        """Wire bytes of the whole payload (fields at their wire dtypes)."""
        return int(sum(a.size * jnp.dtype(a.dtype).itemsize
                       for a in payload.arrays.values()))

    def _check(self, domain: str) -> None:
        if domain not in _DOMAINS:
            raise ValueError(f"domain must be one of {_DOMAINS}, "
                             f"got {domain!r}")

    def __repr__(self) -> str:
        return f"{type(self).__name__}()"


def encode(codec: Union[None, str, "Codec"], x: jnp.ndarray,
           domain: str = "log") -> Payload:
    return as_codec(codec).encode(x, domain=domain)


def decode(payload: Payload) -> jnp.ndarray:
    """Dispatch on the payload's own codec name (decoding never needs the
    encoder's parameters — they are implied by the array shapes)."""
    return get_codec(payload.codec)().decode(payload)


def payload_bytes(payload: Payload) -> int:
    """Wire bytes of ``payload`` — the successor of the old
    ``messenger_bytes``, which merely *asserted* a bf16 cost nothing
    paid."""
    return get_codec(payload.codec)().payload_bytes(payload)


def bytes_per_messenger(payload: Payload) -> float:
    """Wire bytes per encoded messenger (rows share a uniform format)."""
    return payload_bytes(payload) / max(payload.rows, 1)


def gather(payload: Payload, rows) -> Payload:
    """Slice a batched payload down to the given leading-axis rows.

    Every codec is row-independent (per-row affine params, per-sample
    top-k), so ``decode(gather(p, rows)) == decode(p)[rows]`` — the
    server uses this to decode only the rows an upload actually merges
    instead of the whole N-stack."""
    if len(payload.shape) < 3:
        raise ValueError(f"gather needs a batched (N, R, C) payload, got "
                         f"shape {payload.shape}")
    idx = jnp.asarray(rows)
    return Payload(payload.codec, payload.domain,
                   (int(idx.shape[0]),) + tuple(payload.shape[1:]),
                   {k: a[idx] for k, a in payload.arrays.items()})


def assemble(parts: Sequence[Payload], rows: Sequence,
             n: int) -> Payload:
    """Scatter per-cohort payloads into one N-stack payload.

    ``rows[i]`` are the global client indices of ``parts[i]``'s leading
    axis. Un-owned rows stay zero — they are masked out of the merge on
    ingest, exactly like the pre-wire zeros-stack."""
    if not parts:
        raise ValueError("assemble needs at least one part")
    first = parts[0]
    for p in parts[1:]:
        if p.codec != first.codec or p.domain != first.domain or \
                p.shape[1:] != first.shape[1:]:
            raise ValueError("assemble: parts disagree on codec/shape")
    base = {k: jnp.zeros((n,) + tuple(a.shape[1:]), a.dtype)
            for k, a in first.arrays.items()}
    for part, ids in zip(parts, rows):
        idx = jnp.asarray(ids)
        for k in base:
            base[k] = base[k].at[idx].set(part.arrays[k])
    return Payload(first.codec, first.domain, (n,) + tuple(first.shape[1:]),
                   base)


# --------------------------------------------------------------------------
# built-in codecs
# --------------------------------------------------------------------------

@register_codec("dense32")
@dataclasses.dataclass(frozen=True)
class Dense32(Codec):
    """fp32 pass-through: the bit-identical oracle every other codec is
    graded against. decode(encode(x)) IS x — same buffer, no cast."""

    def encode(self, x: jnp.ndarray, domain: str = "log") -> Payload:
        self._check(domain)
        x = jnp.asarray(x)
        if x.dtype != jnp.float32:
            x = x.astype(jnp.float32)
        return Payload("dense32", domain, tuple(x.shape), {"data": x})

    def decode(self, payload: Payload) -> jnp.ndarray:
        return payload.arrays["data"]


@register_codec("dense16")
@dataclasses.dataclass(frozen=True)
class Dense16(Codec):
    """bf16 cast (the wire cost the old ``messenger_bytes`` asserted but
    nothing paid). Lossy: decode renormalizes in-domain."""

    def encode(self, x: jnp.ndarray, domain: str = "log") -> Payload:
        self._check(domain)
        data = jnp.asarray(x).astype(jnp.bfloat16)
        return Payload("dense16", domain, tuple(x.shape), {"data": data})

    def decode(self, payload: Payload) -> jnp.ndarray:
        x = payload.arrays["data"].astype(jnp.float32)
        if payload.domain == "log":
            return jax.nn.log_softmax(x, axis=-1)
        return _renorm_probs(x)


@register_codec("int8")
@dataclasses.dataclass(frozen=True)
class Int8(Codec):
    """Per-row affine quantization (one row = one reference sample).

    q = round((x - zp) / scale) in uint8, with per-row ``scale`` and
    ``zero_point`` (the row minimum) stored in bf16: C + 4 wire bytes
    per row vs fp32's 4C. Decode dequantizes and renormalizes in-domain
    — the bf16 rounding of the zero-point is an additive per-row shift,
    which the log-domain softmax renorm cancels exactly."""

    def encode(self, x: jnp.ndarray, domain: str = "log") -> Payload:
        self._check(domain)
        x = jnp.asarray(x, jnp.float32)
        lo = jnp.min(x, axis=-1)
        hi = jnp.max(x, axis=-1)
        # quantize against the bf16-ROUNDED affine params — the exact
        # values the decoder will read off the wire — so encode and
        # decode agree bit-for-bit on the map (quantizing with the fp32
        # scale would add an un-modeled per-row rescale on decode)
        scale = jnp.maximum((hi - lo) / 255.0, 1e-8).astype(jnp.bfloat16)
        zp = lo.astype(jnp.bfloat16)
        q = jnp.clip(jnp.round((x - zp.astype(jnp.float32)[..., None])
                               / scale.astype(jnp.float32)[..., None]),
                     0.0, 255.0).astype(jnp.uint8)
        return Payload("int8", domain, tuple(x.shape),
                       {"q": q, "scale": scale, "zp": zp})

    def decode(self, payload: Payload) -> jnp.ndarray:
        q = payload.arrays["q"].astype(jnp.float32)
        scale = payload.arrays["scale"].astype(jnp.float32)[..., None]
        zp = payload.arrays["zp"].astype(jnp.float32)[..., None]
        deq = q * scale + zp
        if payload.domain == "log":
            return jax.nn.log_softmax(deq, axis=-1)
        return _renorm_probs(deq)

    def pairwise_kl(self, payload: Payload) -> jnp.ndarray:
        """Eq.2 divergence matrix straight off the wire form: the fused
        dequant->KL kernel never materializes the dense fp32 (N, R, C)
        decode (``kernels/dequant_kl.py``)."""
        from repro.kernels import ops
        if payload.domain != "log":
            raise ValueError("pairwise_kl grades log-domain messengers")
        if len(payload.shape) != 3:
            raise ValueError(f"expected an (N, R, C) repository payload, "
                             f"got shape {payload.shape}")
        return ops.int8_pairwise_kl(payload.arrays["q"],
                                    payload.arrays["scale"],
                                    payload.arrays["zp"])


@register_codec("topk")
@dataclasses.dataclass(frozen=True)
class TopK(Codec):
    """Classic soft-label sparsification: keep the ``k`` largest
    probabilities per reference sample (bf16 values + int16 class ids)
    plus one renormalized bf16 tail mass, spread uniformly over the
    unsent classes on decode."""

    k: int = 8

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"topk k must be >= 1, got {self.k}")

    @classmethod
    def from_arg(cls, arg: str) -> "TopK":
        return cls(k=int(arg)) if arg else cls()

    def encode(self, x: jnp.ndarray, domain: str = "log") -> Payload:
        self._check(domain)
        x = jnp.asarray(x, jnp.float32)
        c = x.shape[-1]
        p = jnp.exp(x) if domain == "log" else x
        k = min(self.k, c)
        vals, idx = jax.lax.top_k(p, k)
        tail = jnp.clip(1.0 - jnp.sum(vals, axis=-1), 0.0, 1.0)
        idt = jnp.int16 if c <= jnp.iinfo(jnp.int16).max else jnp.int32
        return Payload("topk", domain, tuple(x.shape),
                       {"idx": idx.astype(idt),
                        "vals": vals.astype(jnp.bfloat16),
                        "tail": tail.astype(jnp.bfloat16)})

    def decode(self, payload: Payload) -> jnp.ndarray:
        shape = tuple(payload.shape)
        c = shape[-1]
        idx = payload.arrays["idx"].astype(jnp.int32)
        vals = payload.arrays["vals"].astype(jnp.float32)
        tail = payload.arrays["tail"].astype(jnp.float32)
        k = idx.shape[-1]
        m = 1
        for d in shape[:-1]:
            m *= int(d)
        base = tail / max(c - k, 1) if k < c else jnp.zeros_like(tail)
        p = jnp.broadcast_to(base.reshape(m, 1), (m, c))
        rows = jnp.arange(m)[:, None]
        p = p.at[rows, idx.reshape(m, k)].set(vals.reshape(m, k))
        p = _renorm_probs(p.reshape(shape))
        if payload.domain == "log":
            return jnp.log(p)
        return p


def _renorm_probs(x: jnp.ndarray) -> jnp.ndarray:
    """Clip to the simplex floor and renormalize rows to sum 1."""
    p = jnp.maximum(x, _PROB_FLOOR)
    return p / jnp.sum(p, axis=-1, keepdims=True)
