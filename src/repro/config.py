"""Central configuration for Dagger-JAX.

Two config families:

* ``ModelConfig`` — describes any of the 10 assigned architectures (plus
  reduced smoke-test variants).  One frozen dataclass drives model building,
  sharding rules, dry-run input specs, and the serving engine.

* ``FabricConfig`` — the Dagger NIC analogue.  Fields are split between
  *hard* configuration (changing them produces a new jit trace — the paper's
  re-synthesis) and *soft* configuration (runtime device scalars — the
  paper's CSR writes).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

# Layer kinds used by hybrid stacks (jamba / xlstm / gemma patterns).
ATTN_GLOBAL = 0
ATTN_LOCAL = 1
MAMBA = 2
SLSTM = 3
MLSTM = 4


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0              # routed experts
    top_k: int = 0
    n_shared: int = 0               # shared (always-on) experts
    d_ff_expert: int = 0            # per-expert FFN width
    capacity_factor: float = 1.25
    router_noise: float = 0.0
    # which layers are MoE: "all", "every_other", or "after:N" (dense first N)
    layer_pattern: str = "all"
    # decode-path dispatch: "dense" (all experts x capacity, EP-friendly)
    # or "gather" (per-assignment expert-weight gather — flop/byte-optimal
    # for tiny decode batches; §Perf hillclimb knob)
    decode_mode: str = "dense"
    # FSDP dim for expert weights: "d" shards d_model (contraction dim of
    # the dispatch einsum -> per-einsum partial-sum all-reduces) or "ff"
    # shards d_ff_expert (keeps h sharded through the GLU, one reduce at
    # the output projection).  §Perf hillclimb knob.
    fsdp_dim: str = "d"


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-style Multi-head Latent Attention dims."""
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 16               # mamba state dim
    d_conv: int = 4
    expand: int = 2
    # xlstm
    xlstm_heads: int = 4
    # selective-scan tiling (§Perf hillclimb knobs): chunk length of the
    # outer scan, and the dtype of the materialized [B,chunk,di,N] state
    chunk: int = 256
    scan_dtype: str = "float32"


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"           # dense | moe | ssm | hybrid | encdec | vlm | audio
    n_layers: int = 12
    d_model: int = 1024
    n_heads: int = 16
    n_kv_heads: int = 16
    head_dim: int = 0               # 0 -> d_model // n_heads
    d_ff: int = 4096
    vocab: int = 32000
    max_seq: int = 131072

    # attention details
    attn_kind: str = "gqa"          # gqa | mla
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    local_window: int = 0           # >0 enables sliding-window layers
    local_pattern: int = 0          # N local layers per 1 global (gemma 5:1)
    logit_softcap: float = 0.0

    # FFN
    mlp_act: str = "swiglu"         # swiglu | gelu | sqrelu | relu
    norm_kind: str = "rmsnorm"      # rmsnorm | layernorm
    tie_embeddings: bool = False

    # mixtures / recurrence
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    # hybrid interleave: length == period; e.g. jamba (ATTN,MAMBA*7)
    hybrid_pattern: Tuple[int, ...] = ()

    # encoder-decoder
    enc_layers: int = 0             # >0 -> enc-dec; n_layers is decoder depth

    # multimodal frontend stub: "" | "audio" | "vision"
    frontend: str = ""
    frontend_tokens: int = 0        # frames / patches per example
    frontend_dim: int = 0           # embedding dim produced by the stub

    # multi-token prediction (deepseek MTP) — extra heads
    mtp_depth: int = 0

    # numerics / memory
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    # checkpointing policy for the layer scan: "dots" (save dot outputs),
    # "nothing" (full recompute), "everything" (no remat)
    remat_policy: str = "dots"
    fsdp: bool = False              # shard params over the data axis too
    use_pallas: bool = False        # route hot paths through Pallas kernels
    # Tensor-parallel mesh axis name for SPMD decode (shard_map): when
    # non-empty, the dense GQA + MLP decode path psums partial outputs
    # over this axis and the embedding/unembedding run vocab-parallel.
    # Only the dense-GQA decode path honors it; param shards must follow
    # ``parallel.sharding.param_specs(..., tp=tp_axis)``.
    tp_axis: str = ""
    # §Perf: compute attention scores via preferred_element_type instead of
    # materializing f32 casts of Q/K/V (saves HBM traffic on decode reads)
    fast_attn: bool = False
    # §Perf: KV-block size for flash (online-softmax) full attention;
    # 0 = dense scores (materializes [B,H,S,S] — the baseline)
    flash_block: int = 0
    # §Perf: constrain the residual stream's sequence dim onto the
    # "model" axis between blocks (sequence parallelism for norms /
    # elementwise; GSPMD inserts the gathers attention needs)
    seq_parallel: bool = False
    # §Perf: re-pin the residual stream's BATCH dim to these mesh axes
    # between blocks (comma-separated, e.g. "data" or "pod,data").
    # Without this, FSDP-sharded weights can make GSPMD replicate the
    # batch at inference (observed: 14x per-device work on phi3 prefill).
    batch_constraint: str = ""

    # decode behaviour
    supports_long_context: bool = False   # run the long_500k cell?

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # -- parameter counting (for roofline MODEL_FLOPS = 6 N D) ------------
    def param_count(self, active_only: bool = False) -> int:
        """Approximate parameter count; active_only counts MoE top-k only."""
        d, f, V = self.d_model, self.d_ff, self.vocab
        hd = self.resolved_head_dim
        nq, nkv = self.n_heads, self.n_kv_heads

        def attn_params() -> int:
            if self.attn_kind == "mla":
                m = self.mla
                qk = m.qk_nope_head_dim + m.qk_rope_head_dim
                p = d * m.q_lora_rank + m.q_lora_rank * nq * qk
                p += d * (m.kv_lora_rank + m.qk_rope_head_dim)
                p += m.kv_lora_rank * nq * (m.qk_nope_head_dim + m.v_head_dim)
                p += nq * m.v_head_dim * d
                return p
            return d * hd * (nq + 2 * nkv) + nq * hd * d

        def dense_ffn() -> int:
            mult = 3 if self.mlp_act in ("swiglu", "geglu") else 2
            return mult * d * f

        def moe_ffn(active: bool) -> int:
            mo = self.moe
            n = (mo.top_k if active else mo.n_experts) + mo.n_shared
            mult = 3 if self.mlp_act in ("swiglu", "geglu") else 2
            return n * mult * d * mo.d_ff_expert + d * mo.n_experts

        def mamba_params() -> int:
            s = self.ssm
            di = s.expand * d
            return 2 * d * di + di * (2 * s.d_state + 2) + di * s.d_conv + di * d

        total = V * d  # embedding
        if not self.tie_embeddings:
            total += V * d
        layers = self._layer_kinds()
        for kind, is_moe in layers:
            if kind in (ATTN_GLOBAL, ATTN_LOCAL):
                total += attn_params()
                total += moe_ffn(active_only) if is_moe else dense_ffn()
            elif kind == MAMBA:
                total += mamba_params()
                total += moe_ffn(active_only) if is_moe else dense_ffn()
            elif kind in (SLSTM, MLSTM):
                total += 4 * d * d + dense_ffn() // 2
        if self.enc_layers:
            # encoder self-attn + ffn + decoder cross-attn already excluded
            total += self.enc_layers * (attn_params() + dense_ffn())
            total += self.n_layers * attn_params()  # cross-attention
        return int(total)

    def _layer_kinds(self):
        """Return [(layer_kind, is_moe)] for the decoder stack."""
        out = []
        for i in range(self.n_layers):
            if self.hybrid_pattern:
                kind = self.hybrid_pattern[i % len(self.hybrid_pattern)]
            elif self.family == "ssm":
                kind = (SLSTM, MLSTM)[i % 2]
            elif self.local_pattern:
                kind = ATTN_GLOBAL if (i % (self.local_pattern + 1)
                                       == self.local_pattern) else ATTN_LOCAL
            else:
                kind = ATTN_GLOBAL
            is_moe = False
            if self.moe is not None:
                pat = self.moe.layer_pattern
                if pat == "all":
                    is_moe = True
                elif pat == "every_other":
                    is_moe = i % 2 == 1
                elif pat.startswith("after:"):
                    is_moe = i >= int(pat.split(":")[1])
            out.append((kind, is_moe))
        return out


# ---------------------------------------------------------------------------
# Fabric (Dagger NIC) configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FabricConfig:
    """Dagger NIC configuration.

    Hard configuration (paper: SystemVerilog macros, needs re-synthesis —
    here: retrace/recompile):
    """
    n_flows: int = 4                # NIC flows == RX/TX ring pairs (paper: <=512)
    ring_entries: int = 64          # slots per RX/TX ring
    slot_bytes: int = 64            # RPC MTU per slot (cache line analogue)
    conn_cache_entries: int = 256   # direct-mapped connection cache size
    interface: str = "upi"          # doorbell | doorbell_batch | mmio | upi
    lb_scheme: str = "round_robin"  # round_robin | static | object_level
    request_buffer_slots: int = 0   # 0 -> B * n_flows (paper §4.4.2)
    threading: str = "dispatch"     # dispatch | worker  (paper Table 4)
    use_pallas: bool = False

    # Soft configuration defaults (paper: CSR writes — here: device scalars):
    batch_size: int = 4             # CCI-P batching width B (paper: B=4 best)
    dynamic_batching: bool = True   # adapt B under load (paper Fig. 11 green)
    active_flows: int = 0           # 0 -> all flows active

    @property
    def resolved_request_buffer_slots(self) -> int:
        return self.request_buffer_slots or self.batch_size * self.n_flows

    def replace(self, **kw) -> "FabricConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Run / launcher configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeCell:
    """One (input-shape) cell of the assignment matrix."""
    name: str                       # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str                       # train | prefill | decode


SHAPES = {
    "train_4k": ShapeCell("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
}


@dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 1000
    microbatches: int = 1           # gradient accumulation
    grad_compression: str = "none"  # none | int8_ef  (cross-pod trick)
    opt_dtype: str = "float32"      # AdamW m/v dtype (bf16 for huge models)
    seed: int = 0


# ---------------------------------------------------------------------------
# Accelerator profiles — environment setup so the same bench commands run
# unmodified on CPU / GPU / TPU
# ---------------------------------------------------------------------------

# Each profile: env vars set BEFORE jax import (setdefault — an explicit
# user environment always wins) plus XLA flags APPENDED to XLA_FLAGS.
# The gpu profile enables the latency-hiding scheduler and async
# collectives so the switch step's exchange collectives overlap with the
# per-tier compute; the cpu profile pins the host platform so container
# GPUs never surprise a reproduction run.  The tpu profile adds no
# flags: a TPU takes compiler flags through LIBTPU_INIT_ARGS (which may
# already hold workarounds and is only ever appended to), never through
# XLA_FLAGS, where libtpu flags abort the process.
ACCEL_PROFILES = {
    "cpu": {
        "env": {"JAX_PLATFORMS": "cpu", "JAX_ENABLE_X64": "0"},
        "xla_flags": [],
    },
    "gpu": {
        "env": {"JAX_ENABLE_X64": "0"},
        "xla_flags": [
            "--xla_gpu_enable_latency_hiding_scheduler=true",
            "--xla_gpu_enable_highest_priority_async_stream=true",
        ],
    },
    "tpu": {
        "env": {"JAX_ENABLE_X64": "0"},
        "xla_flags": [],
    },
}


def apply_accel_profile(name: str) -> dict:
    """Apply an ``ACCEL_PROFILES`` entry to ``os.environ``.

    Must run before the first ``import jax`` to take effect (the bench
    runner's ``--accel-profile`` flag does this; jax is imported lazily
    inside the suite loop).  Env vars are ``setdefault`` so explicit user
    settings win; XLA flags are appended to any existing ``XLA_FLAGS``.
    Returns the applied profile.  Raises ``ValueError`` on unknown names.
    """
    import os
    try:
        prof = ACCEL_PROFILES[name]
    except KeyError:
        raise ValueError(
            f"unknown accel profile {name!r}; "
            f"pick one of {sorted(ACCEL_PROFILES)}") from None
    for k, v in prof["env"].items():
        os.environ.setdefault(k, v)
    if prof["xla_flags"]:
        existing = os.environ.get("XLA_FLAGS", "")
        add = " ".join(fl for fl in prof["xla_flags"] if fl not in existing)
        if add:
            os.environ["XLA_FLAGS"] = (existing + " " + add).strip()
    return prof


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache
    (JAX reads it itself; no other directory is set here); otherwise a
    fixed ``<repo>/.jax_cache``.  A fixed path matters: the path is part
    of the cache key, so a directory that moves never hits."""
    import os
    from pathlib import Path

    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# Device peaks (roofline model)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class HWSpec:
    """Published per-chip peaks of one accelerator kind."""
    name: str
    peak_flops_bf16: float               # FLOP/s
    hbm_bw: float                        # bytes/s
    hbm_bytes: float                     # capacity
    ici_bw_per_link: float               # bytes/s per chip-to-chip link
    vmem_bytes: float
    source: str


# Keyed by ``jax.Device.device_kind``.  A kind missing here is an error
# (``hw_spec``), never a default.
HW_BY_KIND = {
    "TPU v5 lite": HWSpec(
        name="tpu_v5e", peak_flops_bf16=197e12, hbm_bw=819e9,
        hbm_bytes=16e9, ici_bw_per_link=50e9, vmem_bytes=128 * 2 ** 20,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI over 4 links; '
               'VMEM 128 MiB per core (Pallas TPU info)'),
}


def hw_spec(device_kind: str) -> HWSpec:
    """Peaks of the device kind ``device_kind``; raises for a kind with
    no entry in ``HW_BY_KIND``."""
    try:
        return HW_BY_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table entry for device kind {device_kind!r} "
            f"(known: {sorted(HW_BY_KIND)})") from None
