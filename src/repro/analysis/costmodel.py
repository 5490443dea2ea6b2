"""Analytic per-device cost model: FLOPs, HBM bytes, collective bytes.

Why analytic: XLA's ``cost_analysis()`` counts while-loop bodies ONCE
(verified in tests/test_roofline.py), and every production config here runs
its layers — and its attention/SSM chunks — under ``lax.scan``.  The
compiled artifact therefore under-counts by ~n_layers x n_chunks.  Since we
own the model code, the analytic count is exact for the matmul-dominated
terms; tests calibrate it against ``cost_analysis`` on unrolled small
configs.

Sharding-aware: a dimension is divided by a mesh-axis size only when the
rule engine would actually shard it (divisibility), mirroring
:mod:`repro.parallel.sharding`.

All quantities are PER DEVICE PER STEP.  Collective bytes are what crosses
this device's links (ring terms: all-reduce 2(n-1)/n, gather/scatter
(n-1)/n of the payload).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

from repro.configs.base import ArchConfig, ShapeConfig


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    data: int
    model: int
    pod: int = 1

    @property
    def n_chips(self) -> int:
        return self.data * self.model * self.pod

    @property
    def dp(self) -> int:      # total data-parallel ways (batch divides this)
        return self.data * self.pod


@dataclasses.dataclass
class CostReport:
    flops: float                 # per device
    hbm_bytes: float             # per device
    collective_bytes: float      # per device, on-link
    breakdown: Dict[str, float]
    model_flops: float           # 6*N*D (dense) / 6*N_active*D (MoE), global
    params_bytes_per_chip: float


def _eff(n: int, ways: int) -> float:
    """Divide only if the rule engine would shard (divisibility)."""
    return n / ways if (ways > 1 and n % ways == 0) else float(n)


def _dtype_bytes(cfg: ArchConfig) -> int:
    return 2 if cfg.dtype == "bfloat16" else 4


def _mamba2_widths(cfg: ArchConfig) -> Tuple[int, int]:
    """(inner width, B or C width) of a Mamba-2 mixer."""
    return cfg.ssm_heads * cfg.ssm_head_dim, cfg.ssm_groups * cfg.ssm_state


def param_count(cfg: ArchConfig) -> Tuple[float, float]:
    """(total, active) parameter counts."""
    D, F, L, V = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab_size
    dh, Hq, Hkv = cfg.head_dim_, cfg.n_heads, cfg.n_kv_heads
    n = 0.0
    if cfg.input_kind == "tokens":
        n += V * D
    else:
        n += D * D
    n += D * (cfg.n_codebooks or 1) * V     # lm head
    per_layer = 0.0
    if cfg.family != "ssm":
        per_layer += D * (Hq + 2 * Hkv) * dh + Hq * dh * D
    if cfg.family in ("dense", "audio", "vlm", "hybrid") or cfg.dense_residual:
        per_layer += 3 * D * F
    moe_per_layer = 0.0
    if cfg.n_experts:
        moe_per_layer = 3 * cfg.n_experts * D * cfg.moe_d_ff + D * cfg.n_experts
        per_layer += moe_per_layer
    if cfg.family == "ssm":
        per_layer += 5 * D * D          # wr wk wv wg wo
        per_layer += 2 * D * F + D * D  # channel mix
        per_layer += 2 * 64 * D         # decay lora
    if cfg.family == "hybrid" and cfg.ssm_heads:
        di, gn = _mamba2_widths(cfg)
        per_layer += D * (2 * di + 2 * gn + cfg.ssm_heads) + di * D  # mamba-2
    elif cfg.family == "hybrid":
        per_layer += 2 * D * D + D * (2 * cfg.ssm_state + 1) + D * D  # mamba
    total = n + L * per_layer
    active = total
    if cfg.n_experts:
        active_moe = 3 * cfg.top_k * D * cfg.moe_d_ff + D * cfg.n_experts
        active = total - L * moe_per_layer + L * active_moe
    return total, active


def step_costs(cfg: ArchConfig, shape: ShapeConfig, mesh: MeshSpec,
               optimizer_bytes_per_param: float = 8.0) -> CostReport:
    """Per-device roofline quantities for one (train|prefill|decode) step."""
    D, F, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    dh, Hq, Hkv = cfg.head_dim_, cfg.n_heads, cfg.n_kv_heads
    V = cfg.vocab_size * (cfg.n_codebooks or 1)
    tp, dp = mesh.model, mesh.dp
    if cfg.exec_policy.moe_pure_dp:
        # pure-DP profile: the whole mesh is data-parallel, no TP axes
        tp, dp = 1, mesh.n_chips
    dt = _dtype_bytes(cfg)
    kv_dt = 1 if cfg.kv_cache_bits == 8 else dt

    B = shape.global_batch
    S = 1 if shape.is_decode else shape.seq_len
    ctx = shape.seq_len                       # context length attended over
    # per-device tokens (batch shards over dp when divisible)
    T = (_eff(B, dp)) * S
    bk = {}

    train = shape.kind == "train"
    bwd_mult = 3.0 if train else 1.0          # fwd + ~2x bwd

    # ---- linear algebra ----------------------------------------------------
    if cfg.family != "ssm":
        qf = 2 * T * D * _eff(Hq * dh, tp)
        kvf = 2 * 2 * T * D * _eff(Hkv * dh, tp)
        of = 2 * T * _eff(Hq * dh, tp) * D
        bk["qkvo"] = (qf + kvf + of) * L * bwd_mult
        # attention: causal halves the averaged context for full-seq
        # passes.  Compute shards over tp via heads when aligned, else via
        # the query-sequence dim (verified against compiled HLO: scores
        # dots carry S/tp query rows when heads don't divide).
        if shape.is_decode:
            eff_ctx = min(ctx, cfg.sliding_window) if (
                cfg.sliding_window and cfg.supports_long_context
                and ctx > 65536) else ctx
            # decode: the cache seq dim is sharded over tp (dryrun
            # _STATE_AXES), so per-device context shards too
            att = 2 * 2 * T * _eff(eff_ctx, tp) * Hq * dh
        else:
            if Hq % tp == 0:
                att = 2 * 2 * T * (ctx / 2) * (Hq / tp) * dh
            elif S % tp == 0 and tp > 1:
                att = 2 * 2 * (T / tp) * (ctx / 2) * Hq * dh
            else:
                att = 2 * 2 * T * (ctx / 2) * Hq * dh
        bk["attention"] = att * L * bwd_mult
    if cfg.family in ("dense", "audio", "vlm", "hybrid") or cfg.dense_residual:
        bk["ffn"] = 3 * 2 * T * D * _eff(F, tp) * L * bwd_mult
    if cfg.n_experts:
        cf = cfg.capacity_factor
        tok = T * cfg.top_k * cf
        bk["moe_ffn"] = 3 * 2 * tok * D * _eff(cfg.moe_d_ff, tp) * L * bwd_mult
        bk["router"] = 2 * T * D * cfg.n_experts * L * bwd_mult
    if cfg.family == "ssm":
        bk["rwkv_proj"] = 5 * 2 * T * D * _eff(D, tp) * L * bwd_mult
        bk["rwkv_rec"] = 8 * T * D * dh * L * bwd_mult
        bk["rwkv_cm"] = (2 * 2 * T * D * _eff(F, tp) +
                         2 * T * D * _eff(D, tp)) * L * bwd_mult
    if cfg.family == "hybrid" and cfg.ssm_heads:
        di, gn = _mamba2_widths(cfg)
        N = cfg.ssm_state
        bk["mamba"] = ((2 * T * D * _eff(2 * di + 2 * gn + cfg.ssm_heads, tp))
                       + (2 * T * cfg.ssm_conv * (di + 2 * gn))
                       + (6 * T * di * N)
                       + (2 * T * _eff(di, tp) * D)) * L * bwd_mult
    elif cfg.family == "hybrid":
        N = cfg.ssm_state
        bk["mamba"] = ((2 * T * D * _eff(2 * D, tp)) +
                       (2 * T * cfg.ssm_conv * D) +
                       (2 * T * D * (2 * N + 1)) +
                       (6 * T * D * N) +
                       (2 * T * D * _eff(D, tp))) * L * bwd_mult
    bk["lm_head"] = 2 * T * D * _eff(V, tp) * (bwd_mult if train else
                                               (1.0 if not shape.is_decode
                                                else 1.0))
    flops = sum(bk.values())

    # ---- parameters & optimizer --------------------------------------------
    total_p, active_p = param_count(cfg)
    # params shard over tp (and experts additionally over dp via expert_mlp
    # fallback only when tp can't take them; approximate: /n_chips for MoE
    # expert slabs when both axes divide, else /tp).
    if cfg.n_experts and cfg.n_experts % tp == 0:
        params_dev = total_p / tp
    else:
        params_dev = total_p / tp
    params_bytes = params_dev * dt
    opt_bytes = params_dev * (optimizer_bytes_per_param if train else 0.0)

    # ---- HBM bytes ----------------------------------------------------------
    act_unit = T * D * dt
    weight_reads = params_bytes * (3.0 if train else 1.0)
    act_traffic = act_unit * 12 * L * (2.0 if train else 1.0)
    hbm = weight_reads + act_traffic + opt_bytes * (1.0 if train else 0.0)
    if shape.is_decode and cfg.family != "ssm":
        cache_len = min(ctx, cfg.sliding_window) if (
            cfg.sliding_window and cfg.supports_long_context
            and ctx > 65536) else ctx
        # cache sequence dim shards over tp (launch/dryrun _STATE_AXES)
        kv_dev = (L * _eff(B, dp) * _eff(cache_len, tp) *
                  Hkv * dh * 2 * kv_dt)
        hbm += kv_dev  # full cache streamed once per decoded token
        bk["kv_cache_bytes"] = kv_dev
    if cfg.family in ("ssm", "hybrid") and shape.is_decode:
        width = (Hq * dh * dh if cfg.family == "ssm"
                 else _mamba2_widths(cfg)[0] * cfg.ssm_state if cfg.ssm_heads
                 else D * cfg.ssm_state)
        hbm += L * _eff(B, dp) * width * 4

    # ---- collectives ---------------------------------------------------------
    coll = 0.0
    ar = lambda payload, n: 2 * payload * (n - 1) / n if n > 1 else 0.0
    # TP activation all-reduces: 2/layer fwd (+2 bwd when training)
    n_ar = (4 if train else 2) * L
    if tp > 1 and cfg.family != "ssm":
        coll += n_ar * ar(act_unit, tp)
    if tp > 1 and cfg.family == "ssm":
        coll += n_ar * ar(act_unit, tp)
    # vocab-sharded logits: logsumexp partial reduction (fp32 scalars/token)
    if tp > 1:
        coll += ar(T * 4, tp) * 2
    # MoE (shard_map, see models/moe.py): tokens NEVER cross devices in
    # either mode — each (data, model) device routes its local tokens.
    # What crosses:
    #   * the output psum over model (activation-sized, fwd; bwd is a
    #     broadcast) in both EP and expert-TP modes,
    #   * EP+FSDP: the expert-weight all-gathers (fwd + recompute in bwd)
    #     and the grad reduce-scatter back.
    # (The earlier dispatch-crossing model over-counted granite 5.3x —
    # refuted against HLO-parsed collectives; see EXPERIMENTS.md §Perf.)
    if cfg.n_experts and tp > 1:
        coll += ar(T * D * dt, tp) / 2 * L * (2.0 if train else 1.0)
        if cfg.fuse_moe_ffn_ar and cfg.dense_residual:
            # dense-residual FFN shares the MoE psum: one fwd AR saved/layer
            coll -= ar(T * D * dt, tp) / 2 * L * (1.0 if train else 1.0)
        ep_mode = cfg.n_experts % tp == 0
        fsdp_ways = mesh.dp
        expert_bytes = 3 * (cfg.n_experts / tp) * D * cfg.moe_d_ff * dt
        big = cfg.n_experts * D * cfg.moe_d_ff * cfg.n_layers > 4e9
        if ep_mode and big and cfg.moe_d_ff % fsdp_ways == 0:
            gather = expert_bytes * (fsdp_ways - 1) / fsdp_ways
            if cfg.exec_policy.fsdp_int8_gather:
                gather *= (0.5 if dt == 2 else 0.25)  # FxP8 transport
            # fwd gather + bwd re-gather (remat) + grad reduce-scatter
            coll += gather * (3.0 if train else 1.0) * L
    # DP gradient all-reduce (hierarchical on multi-pod: intra-pod RS/AG at
    # full shard size + inter-pod AR at 1/data the bytes)
    if train and dp > 1:
        grad_bytes = params_dev * dt
        if mesh.pod > 1:
            coll += ar(grad_bytes, mesh.data)
            coll += ar(grad_bytes / mesh.data, mesh.pod)
        else:
            coll += ar(grad_bytes, mesh.data)
        bk["dp_grad_bytes"] = grad_bytes
    mf = 6 * active_p * (B * shape.seq_len) if train else \
        2 * active_p * (B * S)
    return CostReport(flops=flops, hbm_bytes=hbm, collective_bytes=coll,
                      breakdown=bk, model_flops=mf,
                      params_bytes_per_chip=params_bytes + opt_bytes)
