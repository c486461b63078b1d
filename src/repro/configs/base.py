"""Architecture + shape configuration system.

Every assigned architecture is one `ArchConfig` in `repro/configs/<id>.py`,
selectable by ``--arch <id>`` in the launchers.  Shapes (train_4k /
prefill_32k / decode_32k / long_500k) are `ShapeConfig`s; applicability of a
shape to an arch is decided by `cells()` (DESIGN.md §4 skip rules).
"""

from __future__ import annotations

import dataclasses
import importlib
import math
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """A mixture-of-experts FFN as one chip holds it.

    The router spans all ``num_experts`` (the published count) and picks
    ``top_k`` per token; the chip holds experts ``expert_offset ..
    expert_offset + held - 1`` (all of them when ``num_held`` is None) and
    computes their part of the result, plus ``n_shared`` always-on experts.
    ``scoring`` "softmax" is Qwen3-MoE's router; "sigmoid" with ``n_group``
    groups, of which the ``topk_group`` best (each scored by its two best
    experts) are kept, is DeepSeek-V3's. The chosen weights are renormalized
    to sum to one and multiplied by ``routed_scale``. The first
    ``first_k_dense`` layers of the model have a dense MLP instead.
    ``bias_std`` sets the random draw that stands in for the sigmoid router's
    trained correction bias."""
    num_experts: int = 128
    top_k: int = 8
    d_ff_expert: int = 1536          # per-expert FFN hidden
    num_held: Optional[int] = None   # experts held here (None: all)
    expert_offset: int = 0           # the first held expert
    n_shared: int = 0                # shared experts, d_ff_expert wide each
    scoring: str = "softmax"         # softmax | sigmoid
    n_group: int = 1
    topk_group: int = 1
    routed_scale: float = 1.0
    first_k_dense: int = 0
    bias_std: float = 0.0            # drawn correction bias (sigmoid): N(0, std^2)

    @property
    def held(self) -> int:
        return self.num_experts if self.num_held is None else self.num_held


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN RoPE scaling (``rope_scaling`` of type "yarn"): rotary
    frequencies blended between the extrapolated and the interpolated
    (divided by ``factor``) ones over the dimensions between ``beta_fast``
    and ``beta_slow`` rotations at ``original_max_position``."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0

    def _m(self, m: float) -> float:
        return 0.1 * m * math.log(self.factor) + 1.0 if self.factor > 1 else 1.0

    @property
    def cos_sin_scale(self) -> float:
        return self._m(self.mscale) / self._m(self.mscale_all_dim)

    @property
    def softmax_mult(self) -> float:
        """Factor on the attention's softmax scale: mscale(all_dim)^2."""
        return self._m(self.mscale_all_dim) ** 2 if self.mscale_all_dim else 1.0


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 768
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64
    yarn: Optional[YaRN] = None      # None: plain RoPE

    @property
    def softmax_scale(self) -> float:
        s = 1.0 / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)
        return s * self.yarn.softmax_mult if self.yarn else s


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    n_groups: int = 1
    chunk: int = 256                 # SSD chunk length


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    # RecurrentGemma / Griffin: repeating (recurrent, recurrent, attention)
    pattern: Tuple[str, ...] = ("rglru", "rglru", "local_attn")
    window: int = 2048
    lru_width: Optional[int] = None  # defaults to d_model
    conv_width: int = 4


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense | moe | vlm | hybrid | audio | ssm
    num_layers: int
    d_model: int
    num_heads: int                   # 0 for attention-free
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    encoder_only: bool = False       # hubert: no decode phase
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid: Optional[HybridConfig] = None
    # modality frontend stubs (DESIGN.md §4): precomputed embeddings
    frontend: Optional[str] = None   # None | 'vision_patches' | 'audio_frames'
    frontend_dim: int = 0            # dim of precomputed frontend features
    frontend_len: int = 256          # frontend positions per example
    source: str = ""

    # ---- derived -----------------------------------------------------------
    @property
    def attention_free(self) -> bool:
        return self.ssm is not None

    @property
    def sub_quadratic(self) -> bool:
        """True if long-context (500k) serving is in scope (DESIGN.md §4)."""
        return self.ssm is not None or self.hybrid is not None

    def param_count(self) -> int:
        """Approximate parameter count (embedding + blocks + head)."""
        d, l = self.d_model, self.num_layers
        n = self.vocab_size * d  # embed
        if not self.tie_embeddings and not self.encoder_only:
            n += d * self.vocab_size
        if self.ssm is not None:
            di = self.ssm.expand * d
            heads = di // self.ssm.head_dim
            per = (d * (2 * di + 2 * self.ssm.n_groups * self.ssm.d_state + heads)
                   + di * d + 3 * heads + di * self.ssm.conv_width)
            n += l * per
            return n
        hd = self.head_dim
        if self.mla is not None:
            m = self.mla
            per_attn = (d * m.q_lora_rank
                        + m.q_lora_rank * self.num_heads * (m.qk_nope_head_dim + m.qk_rope_head_dim)
                        + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                        + m.kv_lora_rank * self.num_heads * (m.qk_nope_head_dim + m.v_head_dim)
                        + self.num_heads * m.v_head_dim * d)
        else:
            per_attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd \
                + self.num_heads * hd * d
        per_ffn = 3 * d * self.d_ff
        if self.moe is not None:
            m = self.moe
            bias = m.num_experts if m.scoring == "sigmoid" else 0
            per_moe = (d * m.num_experts + bias
                       + (m.held + m.n_shared) * 3 * d * m.d_ff_expert)
            n += m.first_k_dense * (per_attn + per_ffn)
            l -= m.first_k_dense
            per_ffn = per_moe
        if self.hybrid is not None:
            h = self.hybrid
            lru = h.lru_width or d
            n_rec = sum(1 for i in range(l) if h.pattern[i % len(h.pattern)] == "rglru")
            n_att = l - n_rec
            per_rec = d * lru * 2 + lru * d + lru * h.conv_width + 3 * lru + per_ffn
            per_att = per_attn + per_ffn
            n += n_rec * per_rec + n_att * per_att
            return n
        n += l * (per_attn + per_ffn)
        return n

    def active_param_count(self) -> int:
        """MoE: only top_k of the held experts fire per token."""
        if self.moe is None:
            return self.param_count()
        m, d = self.moe, self.d_model
        l = self.num_layers - m.first_k_dense
        routed = 3 * d * m.d_ff_expert
        return self.param_count() - l * (m.held - min(m.top_k, m.held)) * routed

    def with_layers(self, num_layers: int) -> "ArchConfig":
        """Same config at a different depth (dry-run cost extrapolation).
        For hybrid archs, pass a multiple of the block pattern length."""
        return dataclasses.replace(self, num_layers=num_layers)

    def reduced(self) -> "ArchConfig":
        """Small same-family config for CPU smoke tests."""
        kw = dict(
            name=self.name + "-reduced",
            family=self.family,
            num_layers=min(self.num_layers, 2),
            d_model=128,
            num_heads=min(self.num_heads, 4) if self.num_heads else 0,
            num_kv_heads=min(self.num_kv_heads, 2) if self.num_kv_heads else 0,
            d_ff=256,
            vocab_size=128,
            head_dim=32,
            encoder_only=self.encoder_only,
            frontend=self.frontend,
            frontend_dim=min(self.frontend_dim, 64) if self.frontend_dim else 0,
            frontend_len=8,
        )
        if self.moe is not None:
            kw["moe"] = MoEConfig(num_experts=8, top_k=2, d_ff_expert=64)
        if self.mla is not None:
            kw["mla"] = MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                                  qk_nope_head_dim=16, qk_rope_head_dim=8,
                                  v_head_dim=16)
        if self.ssm is not None:
            kw["ssm"] = SSMConfig(d_state=16, head_dim=16, expand=2,
                                  conv_width=4, chunk=8)
        if self.hybrid is not None:
            kw["hybrid"] = HybridConfig(window=8, lru_width=128)
            kw["num_layers"] = 3  # one full (rglru, rglru, local_attn) pattern
        return ArchConfig(**kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # 'train' | 'prefill' | 'decode'


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

ARCH_IDS = (
    "minitron-4b",
    "smollm-135m",
    "llama3.2-3b",
    "minicpm3-4b",
    "qwen3-moe-235b-a22b",
    "qwen3-moe-30b-a3b",
    "pixtral-12b",
    "recurrentgemma-9b",
    "hubert-xlarge",
    "mamba2-2.7b",
)

_MODULES = {a: "repro.configs." + a.replace("-", "_").replace(".", "_") for a in ARCH_IDS}
_MODULES["qwen3-32b"] = "repro.configs.qwen3_32b"  # paper's own eval model


def get_config(arch: str) -> ArchConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(_MODULES[arch]).CONFIG


def shape_applicable(cfg: ArchConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """DESIGN.md §4 skip rules.  Returns (applicable, reason_if_not)."""
    if shape.kind == "decode" and cfg.encoder_only:
        return False, "encoder-only arch has no autoregressive decode step"
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, "524k context requires sub-quadratic attention (SSM/hybrid only)"
    return True, ""


def cells(arch_ids=ARCH_IDS):
    """All live (arch, shape) dry-run cells."""
    out = []
    for a in arch_ids:
        cfg = get_config(a)
        for s in SHAPES.values():
            ok, _ = shape_applicable(cfg, s)
            if ok:
                out.append((a, s.name))
    return out
