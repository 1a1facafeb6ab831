"""Shared building blocks for the model zoo.

Pure-JAX (no flax/haiku): params are nested dicts of jnp arrays, every module
is a pair of functions ``init_*(key, ...) -> params`` / ``apply(params, x)``.
All matmuls accumulate in fp32 via ``preferred_element_type`` so bf16 params
stay numerically sane on the MXU.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Params = Any  # nested dict pytree of jnp arrays


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config to rule the whole assigned-architecture zoo.

    ``layer_pattern`` is the repeating unit of per-layer mixer types, e.g.
    ``("local","local","local","local","local","global")`` for gemma3's 5:1.
    Valid layer kinds: "global", "local", "mla", "ssd", "rec", and "moe"
    (Nemotron-H's expert layer, a single-op block of its own: a pattern
    that holds it gives no other layer an FFN).
    """

    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None   # default d_model // n_heads
    # attention
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    sliding_window: int = 0          # window for "local" layers (0 = unused)
    use_rope: bool = True            # False: no positional encoding (Nemotron-H)
    layer_pattern: Tuple[str, ...] = ("global",)
    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    moe_top_k: int = 0
    # "moe" layers (sigmoid router, relu^2 experts; DeepSeek-V3 routing)
    shared_d_ff: int = 0             # shared expert width
    routed_scale: float = 1.0        # routed_scaling_factor
    experts_held: int = 0            # experts this chip holds (0 = all)
    # MLA (DeepSeek-V2)
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    rope_head_dim: int = 0           # decoupled rope dim per head
    v_head_dim: int = 0
    # SSM (Mamba-2 / SSD)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 0            # >0: d_inner = ssm_heads * ssm_head_dim
    ssm_groups: int = 1              # B/C groups; also the gated norm's groups
    conv_width: int = 4
    ssm_chunk: int = 256
    # RG-LRU (RecurrentGemma)
    lru_width: int = 0
    # modality frontend stub ("vision" | "audio" | None)
    frontend: Optional[str] = None
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    param_dtype: Any = jnp.bfloat16
    # citation for the assigned-architecture provenance
    source: str = ""

    # ----- derived -----
    @property
    def hd(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def n_groups(self) -> int:
        return self.n_layers // len(self.layer_pattern)

    @property
    def n_remainder(self) -> int:
        return self.n_layers - self.n_groups * len(self.layer_pattern)

    @property
    def d_inner(self) -> int:
        """SSD inner width: heads x head width where the head width is
        given (Mamba-2's ``mamba_num_heads * mamba_head_dim``), else
        ``ssm_expand * d_model``."""
        if self.ssm_head_dim:
            return self.ssm_heads * self.ssm_head_dim
        return self.ssm_expand * self.d_model

    @property
    def n_held(self) -> int:
        """Routed experts whose weights this layer holds."""
        return self.experts_held or self.n_experts

    def param_count(self, params: Params) -> int:
        return sum(int(x.size) for x in jax.tree.leaves(params))

    def active_params_per_token(self) -> int:
        """Analytic N_active for 6·N·D roofline cross-checks."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        per_layer = 0
        for kind in _full_pattern(self):
            if kind in ("global", "local"):
                per_layer += d * self.n_heads * self.hd          # q
                per_layer += 2 * d * self.n_kv_heads * self.hd   # k, v
                per_layer += self.n_heads * self.hd * d          # o
            elif kind == "mla":
                r, qr = self.kv_lora_rank, self.q_lora_rank
                rh, vh = self.rope_head_dim, self.v_head_dim or self.hd
                per_layer += d * (r + rh)                       # kv down (+rope)
                per_layer += r * self.n_heads * (self.hd + vh)  # kv up
                if qr:
                    per_layer += d * qr + qr * self.n_heads * (self.hd + rh)
                else:
                    per_layer += d * self.n_heads * (self.hd + rh)
                per_layer += self.n_heads * vh * d              # o
            elif kind == "ssd":
                di = self.d_inner
                per_layer += d * (2 * di + 2 * self.ssm_groups
                                  * self.ssm_state + self.ssm_heads)
                per_layer += di * d
            elif kind == "rec":
                w = self.lru_width or d
                per_layer += 2 * d * w + w * d + 2 * w
            elif kind == "moe":
                per_layer += d * self.n_experts                 # router
                per_layer += self.moe_top_k * 2 * d * f         # up, down
                per_layer += 2 * d * self.shared_d_ff
                continue
            if "moe" in self.layer_pattern:
                continue                # single-op blocks: no ffn
            # ffn (except pure ssd layers which have none in mamba2)
            if kind != "ssd" or self.d_ff > 0:
                if self.is_moe:
                    active_e = self.moe_top_k + self.n_shared_experts
                    per_layer += active_e * 3 * d * f
                elif self.d_ff > 0:
                    per_layer += 3 * d * f
        return per_layer + 2 * v * d  # embed + head


def _full_pattern(cfg: ModelConfig) -> Sequence[str]:
    pat = list(cfg.layer_pattern) * cfg.n_groups
    pat += list(cfg.layer_pattern)[: cfg.n_remainder]
    return pat


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, fan_in: Optional[int] = None):
    fan = fan_in if fan_in is not None else shape[0]
    scale = 1.0 / math.sqrt(max(fan, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: Params, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def swiglu(gate: jnp.ndarray, up: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.silu(gate) * up


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)          # (head_dim//2,)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                           # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (...,S,hd/2)
    cos = jnp.cos(angles)[..., :, None, :]                  # (...,S,1,hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Masking helpers
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def causal_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                window: int = 0) -> jnp.ndarray:
    """Boolean (..., Sq, Sk) mask. window>0 adds a sliding-window band."""
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window > 0:
        m &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    return m
