"""The registered model zoo: architecture families as federation cohorts.

The paper's premise is clients of *different architectures* collaborating
through messengers alone — no parameter averaging is even possible across
families. This module turns every architecture in ``repro.models`` into a
federation-ready family behind one registry (mirroring the policy / codec /
trigger registries):

  * ``@register_family(name)`` registers a builder
    ``(in_dim, n_classes) -> (init_fn, apply_fn)`` plus a per-family
    default optimizer;
  * ``build_zoo("mlp-s,resnet,transformer", in_dim, n_classes)`` resolves
    names into the ``{name: (init_fn, apply_fn)}`` mapping both engines
    consume (a plain ``Mapping`` — legacy dict zoos keep working), with
    the per-family optimizers riding along as ``zoo.optimizers``;
  * ``parse_assignment("mlp-s:0.5,resnet:0.3,transformer:0.2", ...)``
    turns a weighted spec (the paper's Table-I #ResNet8/20/50 ratios) or
    a plain round-robin list into the per-client family assignment.

Sequence architectures (transformer / ssm / rglru) see flat healthcare
feature vectors through a shared patch adapter: the ``in_dim`` features
are zero-padded to ``S * patch``, reshaped to ``(B, S, patch)`` tokens,
linearly embedded to ``d_model``, mixed, mean-pooled, and classified.
The ResNet-1D family reads the raw series directly (``apply_resnet1d``
adds the channel axis itself). ``nemotron-h`` is Nemotron-H's hybrid
stack (Mamba-2, expert and GQA layers, ``models/transformer.py``) at the
published widths of Nemotron-3-Nano, over tokens of 25 samples. The MLP tiers are byte-for-byte the
``hetero_mlp_zoo`` configs, so MLP-only federations built through the
registry reproduce the pinned trajectories bit-identically.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import math

import jax
import jax.numpy as jnp

from repro.models import transformer as tfm
from repro.models.attention import attn_forward, init_attention
from repro.models.common import (ModelConfig, dense_init, init_rmsnorm,
                                 rmsnorm)
from repro.models.mlp import MLPConfig, mlp_family
from repro.models.resnet import ResNet1DConfig, resnet1d_family
from repro.models.rglru import init_rglru, rglru_forward
from repro.models.ssm import init_ssd, ssd_forward
from repro.optim import Optimizer, adam, sgd

FamilyFns = Tuple[Callable, Callable]           # (init_fn, apply_fn)
Builder = Callable[[int, int], FamilyFns]       # (in_dim, n_classes) -> fns


@dataclasses.dataclass(frozen=True)
class FamilySpec:
    """One registered architecture family.

    ``tier`` is a human hint for which device class the family suits
    (wearable / phone / hospital server) — documentation, not dispatch.
    ``make_optimizer`` returns a FRESH per-cohort default optimizer;
    an explicit ``optimizer=`` at engine build time overrides it."""
    name: str
    builder: Builder
    make_optimizer: Callable[[], Optimizer]
    tier: str = ""


_FAMILIES: Dict[str, FamilySpec] = {}


def register_family(name: str, *, optimizer: Optional[Callable[[], Optimizer]]
                    = None, tier: str = ""):
    """Decorator registering ``(in_dim, n_classes) -> (init, apply)``."""

    def deco(builder: Builder) -> Builder:
        if name in _FAMILIES:
            raise ValueError(f"family {name!r} already registered")
        make_opt = optimizer or (lambda: sgd(0.05, momentum=0.9))
        _FAMILIES[name] = FamilySpec(name, builder, make_opt, tier)
        return builder

    return deco


def registered_families() -> Tuple[str, ...]:
    return tuple(sorted(_FAMILIES))


def get_family(name: str) -> FamilySpec:
    try:
        return _FAMILIES[name]
    except KeyError:
        raise KeyError(f"unknown model family {name!r}; registered: "
                       f"{', '.join(registered_families())}") from None


def as_family(spec: Union[str, FamilySpec]) -> FamilySpec:
    """Coerce a family name or spec to the registered ``FamilySpec``."""
    if isinstance(spec, FamilySpec):
        return spec
    return get_family(spec)


# ---------------------------------------------------------------------------
# zoo construction
# ---------------------------------------------------------------------------

DEFAULT_ZOO = ("mlp-s", "mlp-m", "mlp-l")


class Zoo(dict):
    """``{family: (init_fn, apply_fn)}`` in registration order, plus the
    per-family default optimizers (``self.optimizers``). A plain dict
    subclass so everything that consumes ``families.items()`` — both
    engines, ``pack_cohort`` call sites, tests — takes it unchanged."""

    def __init__(self):
        super().__init__()
        self.optimizers: Dict[str, Optimizer] = {}


def build_zoo(names: Union[None, str, Sequence[str]], in_dim: int,
              n_classes: int) -> Zoo:
    """Resolve family names into a ``Zoo``. ``names`` is a comma string,
    a sequence, or None (the default MLP tiers)."""
    if names is None:
        names = DEFAULT_ZOO
    elif isinstance(names, str):
        names = tuple(p.strip() for p in names.split(",") if p.strip())
    if not names:
        raise ValueError("zoo spec resolved to zero families")
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate families in zoo spec: {list(names)}")
    zoo = Zoo()
    for name in names:
        spec = get_family(name)
        zoo[name] = spec.builder(in_dim, n_classes)
        zoo.optimizers[name] = spec.make_optimizer()
    return zoo


def parse_assignment(spec: Union[None, str, Sequence[str]],
                     names: Sequence[str], n_clients: int) -> List[str]:
    """Per-client family assignment from a spec string.

    * ``None`` — round-robin over ``names`` (``names[i % len(names)]``);
    * ``"fam,fam,..."`` — round-robin over the listed families;
    * ``"fam:w,fam:w,..."`` — weighted shares (the paper's Table-I
      ratios), realized deterministically: client ``i`` goes to the
      family with the largest outstanding deficit ``w_f*(i+1) - count_f``
      (first-listed wins ties), so prefixes are stable and every run of
      the same spec produces the same assignment;
    * a sequence — validated verbatim (must have ``n_clients`` entries).
    """
    names = list(names)
    if not names:
        raise ValueError("assignment needs at least one family")
    if spec is None:
        return [names[i % len(names)] for i in range(n_clients)]
    if not isinstance(spec, str):
        out = list(spec)
        if len(out) != n_clients:
            raise ValueError(f"assignment has {len(out)} entries for "
                             f"{n_clients} clients")
        unknown = sorted(set(out) - set(names))
        if unknown:
            raise ValueError(f"assignment names families not in the zoo: "
                             f"{unknown}; zoo has {names}")
        return out

    parts = [p.strip() for p in spec.split(",") if p.strip()]
    if not parts:
        raise ValueError(f"empty assignment spec {spec!r}")
    weighted = any(":" in p for p in parts)
    fams: List[str] = []
    weights: List[float] = []
    for p in parts:
        fam, colon, w = p.partition(":")
        if weighted and not colon:
            raise ValueError(f"assignment spec mixes weighted and bare "
                             f"entries: {spec!r}")
        if fam not in names:
            raise ValueError(f"assignment names family {fam!r} not in the "
                             f"zoo; zoo has {names}")
        if weighted:
            if fam in fams:
                raise ValueError(f"family {fam!r} listed twice in weighted "
                                 f"spec {spec!r}")
            try:
                wf = float(w)
            except ValueError:
                raise ValueError(f"bad weight {w!r} for family {fam!r} in "
                                 f"{spec!r}") from None
            if wf <= 0:
                raise ValueError(f"weight for family {fam!r} must be > 0, "
                                 f"got {wf}")
            weights.append(wf)
        fams.append(fam)
    if not weighted:
        return [fams[i % len(fams)] for i in range(n_clients)]
    total = sum(weights)
    counts = [0] * len(fams)
    out = []
    for i in range(n_clients):
        deficits = [weights[f] * (i + 1) / total - counts[f]
                    for f in range(len(fams))]
        j = max(range(len(fams)), key=lambda f: (deficits[f], -f))
        counts[j] += 1
        out.append(fams[j])
    return out


# ---------------------------------------------------------------------------
# the MLP capacity tiers (bit-identical to hetero_mlp_zoo)
# ---------------------------------------------------------------------------

_MLP_TIERS = {"mlp-s": (32,), "mlp-m": (64, 64), "mlp-l": (128, 128, 64)}


def _register_mlp(name: str, hidden: Tuple[int, ...], tier: str) -> None:
    @register_family(name, tier=tier)
    def _build(in_dim: int, n_classes: int) -> FamilyFns:
        return mlp_family(MLPConfig(name, in_dim, hidden, n_classes))


_register_mlp("mlp-s", _MLP_TIERS["mlp-s"], "wearable / sensor node")
_register_mlp("mlp-m", _MLP_TIERS["mlp-m"], "phone")
_register_mlp("mlp-l", _MLP_TIERS["mlp-l"], "bedside monitor")


# ---------------------------------------------------------------------------
# ResNet-1D (the paper's own client family)
# ---------------------------------------------------------------------------

@register_family("resnet", tier="bedside monitor")
def _build_resnet(in_dim: int, n_classes: int) -> FamilyFns:
    # width 8 keeps one client ~RESNET8/4 params: CPU-trainable cohorts
    return resnet1d_family(ResNet1DConfig("resnet8-1d-fed", (1, 1, 1), 8,
                                          False, n_classes=n_classes))


# ---------------------------------------------------------------------------
# sequence families: flat features -> (B, S, patch) tokens
# ---------------------------------------------------------------------------

_SEQ_LEN = 8          # fixed token count — tiny, CPU-friendly sequences


def _n_patch(in_dim: int) -> int:
    return -(-in_dim // _SEQ_LEN)


def _to_tokens(x: jnp.ndarray, n_patch: int,
               seq: int = _SEQ_LEN) -> jnp.ndarray:
    """(B, L) flat features -> (B, S, patch), zero-padded tail."""
    x = x.reshape(x.shape[0], -1)
    pad = seq * n_patch - x.shape[1]
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x.reshape(x.shape[0], seq, n_patch)


def _seq_family(cfg: ModelConfig, mixer_init, mixer_fn,
                in_dim: int, n_classes: int) -> FamilyFns:
    """Shared adapter: embed patch tokens, mix, mean-pool, classify."""
    patch = _n_patch(in_dim)
    d = cfg.d_model

    def init_fn(key):
        k_embed, k_mix, k_head = jax.random.split(key, 3)
        return {
            "embed_w": dense_init(k_embed, (patch, d), jnp.float32,
                                  fan_in=patch),
            "embed_b": jnp.zeros((d,), jnp.float32),
            "mixer": mixer_init(k_mix, cfg),
            "head_w": dense_init(k_head, (d, n_classes), jnp.float32,
                                 fan_in=d),
            "head_b": jnp.zeros((n_classes,), jnp.float32),
        }

    def apply_fn(p, x):
        h = _to_tokens(x, patch) @ p["embed_w"] + p["embed_b"]
        h = h + mixer_fn(p["mixer"], cfg, h)
        h = jnp.mean(h, axis=1)
        return h @ p["head_w"] + p["head_b"]

    return init_fn, apply_fn


@register_family("transformer", optimizer=lambda: adam(3e-3),
                 tier="hospital server")
def _build_transformer(in_dim: int, n_classes: int) -> FamilyFns:
    cfg = ModelConfig("fed-transformer-t", "dense", n_layers=1, d_model=16,
                      n_heads=2, n_kv_heads=2, d_ff=0, vocab_size=0,
                      param_dtype=jnp.float32)
    positions = jnp.arange(_SEQ_LEN, dtype=jnp.int32)
    return _seq_family(
        cfg, init_attention,
        lambda p, c, h: attn_forward(p, c, h, positions),
        in_dim, n_classes)


@register_family("ssm", optimizer=lambda: adam(3e-3), tier="phone")
def _build_ssm(in_dim: int, n_classes: int) -> FamilyFns:
    cfg = ModelConfig("fed-ssm-t", "ssm", n_layers=1, d_model=16, n_heads=1,
                      n_kv_heads=1, d_ff=0, vocab_size=0, ssm_state=4,
                      ssm_heads=2, ssm_expand=2, conv_width=2,
                      ssm_chunk=_SEQ_LEN, param_dtype=jnp.float32)
    return _seq_family(cfg, init_ssd, ssd_forward, in_dim, n_classes)


@register_family("rglru", optimizer=lambda: adam(3e-3), tier="wearable")
def _build_rglru(in_dim: int, n_classes: int) -> FamilyFns:
    cfg = ModelConfig("fed-rglru-t", "hybrid", n_layers=1, d_model=16,
                      n_heads=1, n_kv_heads=1, d_ff=0, vocab_size=0,
                      lru_width=16, conv_width=2, param_dtype=jnp.float32)
    return _seq_family(cfg, init_rglru, rglru_forward, in_dim, n_classes)


# ---------------------------------------------------------------------------
# Nemotron-H: Mamba-2, expert and GQA layers over patch tokens
# ---------------------------------------------------------------------------

NEMOTRON_PATCH = 25   # samples a token: 250 ms of a 100 Hz series
# reference samples a gradient block: at the published widths the
# backward over 40 sequences of 120 tokens holds about 1.6 GB
NEMOTRON_REF_BLOCK = 40

_HYBRID_KINDS = {"M": "ssd", "E": "moe", "*": "global"}


def hybrid_pattern(pattern: str) -> Tuple[str, ...]:
    """``hybrid_override_pattern`` letters -> layer kinds."""
    return tuple(_HYBRID_KINDS[c] for c in pattern)


# Nemotron-3-Nano-30B-A3B (huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-
# 30B-A3B-BF16, config.json) at its published widths: its first seven
# layers, one whole period, and this chip's share of each expert layer,
# experts 0-7 of 128 (16 chips splitting each expert layer).
NEMOTRON_H = ModelConfig(
    "nemotron_h", "hybrid", n_layers=7, d_model=2688, n_heads=32,
    n_kv_heads=2, head_dim=128, d_ff=1856, vocab_size=0,
    layer_pattern=hybrid_pattern("MEMEM*E"), use_rope=False,
    n_experts=128, moe_top_k=6, n_shared_experts=1, shared_d_ff=3712,
    routed_scale=2.5, experts_held=8, ssm_state=128, ssm_heads=64,
    ssm_head_dim=64, ssm_groups=8, conv_width=4, ssm_chunk=128,
    norm_eps=1e-5, param_dtype=jnp.float32,
    source="https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")


class ExpertFamilyApply:
    """The ``apply_fn`` of a family with expert layers. Called as
    ``(p, x) -> logits`` like every family's; ``with_stats(p, x)`` also
    gives each expert layer's token-choice count per held expert,
    (n_expert_layers, experts_held). The cohort step
    (``repro.core.client.expert_cohort_step``) returns those counts
    beside the loss, donates the cohort's params and optimizer state and
    differentiates the reference term in blocks of ``ref_block``
    samples; its span carries ``experts_held`` and the tokens stepped
    (``seq_len`` a sample)."""

    def __init__(self, with_stats: Callable, experts_held: int,
                 seq_len: int, ref_block: int = 0):
        self.with_stats = with_stats
        self.experts_held = experts_held
        self.seq_len = seq_len
        self.ref_block = ref_block

    def __call__(self, p, x):
        return self.with_stats(p, x)[0]


def _mamba2_a_dt(key, cfg: ModelConfig, mixer):
    """Mamba-2's init of A and dt: A ~ U[1, 16]; dt log-uniform in
    [1e-3, 1e-1] (floor 1e-4), stored as softplus' inverse."""
    k_a, k_dt = jax.random.split(key)
    shape = mixer["a_log"].shape
    a = jax.random.uniform(k_a, shape, jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(k_dt, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return dict(mixer, a_log=jnp.log(a), dt_bias=dt + jnp.log(-jnp.expm1(-dt)))


def nemotron_h_family(cfg: ModelConfig, in_dim: int, n_classes: int,
                      patch: int = NEMOTRON_PATCH,
                      ref_block: int = NEMOTRON_REF_BLOCK) -> FamilyFns:
    """Patch tokens of ``patch`` samples, linearly embedded; the
    Nemotron-H stack with per-layer rematerialisation; the final RMSNorm;
    mean pool and a class head in place of the LM head. The cohort step
    differentiates its reference term in blocks of ``ref_block``
    samples."""
    seq = -(-in_dim // patch)
    d = cfg.d_model

    def init_fn(key):
        k_embed, k_stack, k_head, k_ssm = jax.random.split(key, 4)
        stack = tfm.init_stack(k_stack, cfg)
        for i, kind in enumerate(cfg.layer_pattern):
            if kind == "ssd":
                pos = stack["groups"][f"pos{i}"]
                pos["mixer"] = _mamba2_a_dt(jax.random.fold_in(k_ssm, i),
                                            cfg, pos["mixer"])
        return {
            "embed_w": dense_init(k_embed, (patch, d), jnp.float32,
                                  fan_in=patch),
            "embed_b": jnp.zeros((d,), jnp.float32),
            "stack": stack,
            "final_norm": init_rmsnorm(d, jnp.float32),
            "head_w": dense_init(k_head, (d, n_classes), jnp.float32,
                                 fan_in=d),
            "head_b": jnp.zeros((n_classes,), jnp.float32),
        }

    def with_stats(p, x):
        h = _to_tokens(x, patch, seq) @ p["embed_w"] + p["embed_b"]
        h, _, loads = tfm.run_stack(p["stack"], cfg, h, remat_layers=True)
        h = jnp.mean(rmsnorm(p["final_norm"], h, cfg.norm_eps), axis=1)
        return h @ p["head_w"] + p["head_b"], loads

    return init_fn, ExpertFamilyApply(with_stats, cfg.n_held, seq,
                                      ref_block)


@register_family("nemotron-h", optimizer=lambda: adam(3e-3),
                 tier="hospital server")
def _build_nemotron_h(in_dim: int, n_classes: int) -> FamilyFns:
    return nemotron_h_family(NEMOTRON_H, in_dim, n_classes)
