"""Config system: architecture + execution + shape descriptors.

Every assigned architecture is a :class:`ArchConfig`; the paper's technique
enters through :class:`ExecutionPolicy` (CORDIC FxP8 matmul path, DA-VINCI
AFs, CAESAR pruning) which every layer consults.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

from repro.core.activations import CordicPolicy
from repro.core.pruning import PruningPolicy
from repro.core.quantization import QuantPolicy


@dataclasses.dataclass(frozen=True)
class ExecutionPolicy:
    """How linear algebra + AFs execute (the RPE's runtime configuration).

    matmul:
      "bf16"         — plain MXU bf16 (reference baseline)
      "fxp8"         — CORDIC-equivalent int8 quantized path (production
                       mapping of the paper's 5-stage FxP8 MAC; W8A8)
      "fxp8_weight"  — W8A16 (weight-only)
      "cordic_kernel"— bit-exact Pallas shift-add kernel (validation scale)
    af: None  => exact float AFs;  CordicPolicy => DA-VINCI CORDIC AFs.
    """

    matmul: str = "bf16"
    af: Optional[CordicPolicy] = None
    pruning: Optional[PruningPolicy] = None
    quant: QuantPolicy = QuantPolicy()
    softmax_cordic: bool = False    # CORDIC softmax in attention (fidelity
                                    # study; exact softmax otherwise)
    moe_pure_dp: bool = False       # treat the whole mesh as data-parallel
                                    # for MoE (small models over-sharded at
                                    # tp=16; see EXPERIMENTS.md #Perf)
    fsdp_int8_gather: bool = False  # FxP8 transport for FSDP expert-weight
                                    # all-gathers (CAESAR co-design on
                                    # collectives)

    def tag(self) -> str:
        parts = [self.matmul]
        if self.af is not None:
            parts.append(f"af{self.af.bits}")
        if self.pruning is not None:
            parts.append(f"p{int(self.pruning.rate * 100)}")
        return "-".join(parts)


BF16_EXEC = ExecutionPolicy()
# Paper-faithful production policy: FxP8 MACs + CORDIC AFs + 40% pruning.
CORDIC_EXEC = ExecutionPolicy(matmul="fxp8", af=CordicPolicy(bits=16),
                              pruning=PruningPolicy(rate=0.40))


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """The one description of a serving cache's storage format.

    Replaces the two historical knobs that grew side by side —
    ``ArchConfig.kv_cache_bits`` (the paper's fixed-scale Q3.4 FxP8 study)
    and ``ArchConfig.cache_quant`` (the per-block-scaled int8 serving
    mode) — with a single validated spec:

      dtype:      "native" (the model compute dtype), "int8" (per-block
                  f32 scales, :mod:`repro.core.quant_cache`) or "fxp8"
                  (legacy fixed Q3.4 scale, ``attention.KV_Q_SCALE``).
      block:      scale-block width in trailing channels for ``int8``
                  (``None`` = one scale per written vector, the
                  serving-safe default; must divide the trailing axis).
      paged:      store slot K/V (and int8 scale leaves) in a shared
                  fixed-size block pool addressed through per-slot block
                  tables instead of a dense ``max_batch x max_seq``
                  allocation (``models/paged.py``).
      page_size:  tokens per pool page when ``paged``; int8 scales are
                  grouped per page, so quantization granularity aligns
                  with the paging granularity by construction.

    Build one directly (``ArchConfig(..., cache=CacheSpec(dtype="int8"))``)
    or let :meth:`ArchConfig.cache_spec` derive it from the legacy
    fields.  Setting ``cache`` *and* a legacy knob is an error — there
    must be exactly one spelling of the cache format in play.
    """

    dtype: str = "native"          # "native" | "int8" | "fxp8"
    block: Optional[int] = None    # int8 scale-block width (None = vector)
    paged: bool = False
    page_size: int = 16

    def __post_init__(self):
        if self.dtype not in ("native", "int8", "fxp8"):
            raise ValueError(
                f"CacheSpec.dtype must be 'native', 'int8' or 'fxp8', "
                f"got {self.dtype!r}")
        if self.block is not None and self.block < 1:
            raise ValueError(f"CacheSpec.block must be >= 1, got "
                             f"{self.block}")
        if self.paged and self.page_size < 1:
            raise ValueError(f"CacheSpec.page_size must be >= 1, got "
                             f"{self.page_size}")
        if self.paged and self.dtype == "fxp8":
            raise ValueError("paged caches support 'native' and 'int8' "
                             "storage; the legacy fixed-scale 'fxp8' "
                             "format is a single-stream study, not a "
                             "serving format")

    @property
    def quantized(self) -> bool:
        return self.dtype == "int8"


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """One architecture from the assigned pool."""

    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    # transformer details
    rope_theta: float = 10000.0
    qkv_bias: bool = False
    norm_eps: float = 1e-5
    activation: str = "silu"       # FFN activation (DA-VINCI selectable)
    tie_embeddings: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    dense_residual: bool = False   # arctic: dense FFN + MoE in parallel
    capacity_factor: float = 1.25
    # SSM / hybrid
    ssm_state: int = 0
    ssm_conv: int = 4
    # Mamba-2 mixer (Falcon-H1): ssm_heads > 0 selects it over hymba's
    # Mamba-1 branch, with inner width ssm_heads * ssm_head_dim, B/C shared
    # by ssm_groups groups of heads, and a chunked SSD scan in prefill
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 128
    # how a hybrid block joins its attention and SSM branches:
    # "norm_mean" (hymba: RMSNorm each branch, then average) | "sum"
    hybrid_fusion: str = "norm_mean"
    sliding_window: int = 0        # hybrid local-attention window
    global_attn_every: int = 0     # hybrid: every k-th layer is global
    # modality stub ("tokens" | "frames")
    input_kind: str = "tokens"
    n_codebooks: int = 0           # musicgen EnCodec codebooks
    # execution
    exec_policy: ExecutionPolicy = BF16_EXEC
    # attention implementation: "auto" | "naive" | "chunked"
    attn_impl: str = "auto"
    attn_chunk: int = 1024
    # Serving-cache storage format.  `cache` (a CacheSpec) is the one
    # spelling going forward; `kv_cache_bits` / `cache_quant` are the two
    # legacy knobs it unifies, kept so existing configs keep loading —
    # setting a legacy knob *and* `cache` raises in `cache_spec()`.
    cache: Optional["CacheSpec"] = None
    kv_cache_bits: int = 16        # LEGACY: 8 => FxP8 (Q3.4) KV cache
    cache_quant: str = "none"      # LEGACY: "int8" => per-block scales
    fuse_moe_ffn_ar: bool = False  # fuse dense-residual FFN into the MoE
                                   # psum (one AR per layer instead of two)
    remat: bool = True
    dtype: str = "bfloat16"

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def cache_spec(self) -> "CacheSpec":
        """The resolved serving-cache format (one source of truth).

        ``cache`` wins when set; otherwise the legacy knobs are
        translated.  Mixing the spellings — a ``CacheSpec`` *and* a
        non-default ``kv_cache_bits``/``cache_quant`` — is an error, as
        is combining the two legacy quantized formats.
        """
        legacy = []
        if self.kv_cache_bits == 8:
            legacy.append("kv_cache_bits=8")
        elif self.kv_cache_bits != 16:
            raise ValueError(f"kv_cache_bits must be 8 or 16, got "
                             f"{self.kv_cache_bits}")
        if self.cache_quant == "int8":
            legacy.append("cache_quant='int8'")
        elif self.cache_quant != "none":
            raise ValueError(f"unknown cache_quant {self.cache_quant!r}; "
                             f"expected 'none' or 'int8'")
        if self.cache is not None:
            if legacy:
                raise ValueError(
                    f"ArchConfig.cache={self.cache} conflicts with the "
                    f"legacy spelling {' + '.join(legacy)}: the cache "
                    f"format has exactly one spelling — drop the legacy "
                    f"knob and put the format in CacheSpec")
            return self.cache
        if len(legacy) == 2:
            raise ValueError(
                "cache_quant='int8' (per-block scales) and "
                "kv_cache_bits=8 (fixed Q3.4 scale) are mutually "
                "exclusive KV-cache formats; use "
                "cache=CacheSpec(dtype=...) to pick one")
        if self.cache_quant == "int8":
            return CacheSpec(dtype="int8")
        if self.kv_cache_bits == 8:
            return CacheSpec(dtype="fxp8")
        return CacheSpec()

    @property
    def supports_long_context(self) -> bool:
        return self.family in ("ssm", "hybrid")

    def fingerprint(self) -> Dict[str, object]:
        """Serving-identity descriptor for snapshot compatibility.

        Two configs with equal fingerprints produce byte-compatible
        decode-state leaves (same shapes, dtypes and compute), so a slot
        snapshot taken under one restores bit-identically under the
        other.  Deliberately *excludes* engine capacity (``max_batch``,
        pool size) — snapshots restore into differently-sized engines —
        and includes everything that alters per-token state or logits:
        architecture dims, family, execution policy tag, and the
        resolved cache format.
        """
        spec = self.cache_spec()
        return {
            "name": self.name, "family": self.family,
            "n_layers": self.n_layers, "d_model": self.d_model,
            "n_heads": self.n_heads, "n_kv_heads": self.n_kv_heads,
            "d_ff": self.d_ff, "vocab_size": self.vocab_size,
            "head_dim": self.head_dim_, "rope_theta": self.rope_theta,
            "ssm_state": self.ssm_state, "ssm_conv": self.ssm_conv,
            "ssm_heads": self.ssm_heads, "ssm_head_dim": self.ssm_head_dim,
            "ssm_groups": self.ssm_groups, "ssm_chunk": self.ssm_chunk,
            "hybrid_fusion": self.hybrid_fusion,
            "sliding_window": self.sliding_window,
            "global_attn_every": self.global_attn_every,
            "n_experts": self.n_experts, "top_k": self.top_k,
            "activation": self.activation, "dtype": self.dtype,
            "exec": self.exec_policy.tag(),
            "cache": {"dtype": spec.dtype, "block": spec.block,
                      "paged": spec.paged, "page_size": spec.page_size},
        }

    def scaled(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ArchConfig":
        """Smoke-test configuration of the same family (tiny dims)."""
        kw = dict(
            n_layers=2,
            d_model=64,
            n_heads=4,
            n_kv_heads=max(1, min(self.n_kv_heads, 2)),
            d_ff=128,
            vocab_size=256,
            head_dim=16,
            sliding_window=min(self.sliding_window, 32) if self.sliding_window else 0,
        )
        if self.n_experts:
            kw.update(n_experts=4, top_k=min(self.top_k, 2), moe_d_ff=32,
                      capacity_factor=2.0)
        if self.ssm_state:
            kw.update(ssm_state=8)
        if self.ssm_heads:
            # two groups of two heads, and prompts of a few chunks
            kw.update(ssm_heads=4, ssm_head_dim=8, ssm_groups=2,
                      ssm_chunk=8)
        if self.n_codebooks:
            kw.update(n_codebooks=2)
        kw["attn_chunk"] = 16
        kw["remat"] = False
        return self.scaled(**kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell (the assigned shape set)."""

    name: str
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


LM_SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}


def shape_applicable(arch: ArchConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether this (arch x shape) cell runs; reason if skipped."""
    if shape.name == "long_500k" and not arch.supports_long_context:
        return False, ("pure full-attention arch: 500k decode would need a "
                       "524288-token dense KV cache per sequence — "
                       "sub-quadratic families only (see DESIGN.md)")
    return True, ""
