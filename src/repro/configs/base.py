"""Config system: frozen dataclasses for models, shapes and meshes.

Every assigned architecture gets one file in this package defining a
``ModelConfig`` (or ``GNNConfig``/``UNetConfig`` for the paper's own models)
registered via :func:`repro.configs.register`.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration (GShard-style capacity routing)."""

    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared_experts: int = 0          # deepseek-moe: always-on shared experts
    first_dense_layers: int = 0        # deepseek-moe: layer 0 is a dense FFN
    capacity_factor: float = 1.25
    router_aux_weight: float = 1e-2    # load-balance auxiliary loss weight


@dataclass(frozen=True)
class SSMConfig:
    """State-space / recurrent block configuration (Mamba2 SSD or xLSTM)."""

    kind: str                          # "mamba2" | "xlstm"
    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    chunk_size: int = 256              # chunked-scan block length
    n_ssm_heads: int = 8               # heads for the scalar-decay recurrence
    slstm_every: int = 4               # xlstm: every Nth block is an sLSTM


@dataclass(frozen=True)
class ModelConfig:
    """A transformer-family architecture (dense / MoE / SSM / hybrid / enc-dec)."""

    name: str
    family: str                        # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None     # None -> d_model // n_heads
    vocab_pad_to: int = 256            # pad embedding/vocab dim for clean sharding
    rope_theta: float = 1e4
    use_rope: bool = True
    qk_norm: bool = False              # qwen3-style per-head RMSNorm on q,k
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    sliding_window: Optional[int] = None
    layer_pattern: str = "global"      # "global" | "alt_local_global"
    norm: str = "rmsnorm"              # "rmsnorm" | "layernorm"
    act: str = "silu"                  # "silu" | "gelu"
    glu: bool = True                   # gated FFN (SwiGLU/GeGLU)
    post_norms: bool = False           # gemma2: post-norms around attn/ffn
    scale_embeddings: bool = False     # gemma2: embeddings * sqrt(d)
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    attn_every: int = 0                # hybrid (zamba2): shared attn block cadence
    is_encoder_decoder: bool = False
    encoder_layers: int = 0
    frontend: Optional[str] = None     # None | "audio" | "vision" (stubbed)
    n_frontend_tokens: int = 0
    tie_embeddings: bool = False
    source: str = ""                   # citation
    # systems knobs
    param_sharding: str = "fsdp_tp"    # "tp" | "fsdp_tp" | "dp" (replicate)
    serve_param_sharding: str = "tp"   # serving has no optimizer state: FSDP
                                       # gathers are pure overhead (SPerf it.2)
    decode_param_sharding: str = ""    # decode override ("" -> serve_...):
                                       # decode is memory-bound, so FSDP
                                       # param sharding can buy HBM cheaply
    dtype: str = "bfloat16"
    remat: str = "full"                # "none" | "dots" | "full" — the paper
                                       # trains with activation checkpointing
    grad_accum: int = 1                # microbatches per step (gradient
                                       # aggregation — the paper's own trick
                                       # applied on the batch axis)
    # long-context policy: can this arch serve long_500k sub-quadratically?
    supports_long_context: bool = False

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return ((self.vocab_size + p - 1) // p) * p

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """A smoke-test-sized variant of the same family (<=2 layers, d<=256)."""
        kw = dict(
            n_layers=2,
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2),
            head_dim=32,
            d_ff=256,
            vocab_size=512,
            vocab_pad_to=64,
            encoder_layers=2 if self.is_encoder_decoder else 0,
            n_frontend_tokens=16 if self.frontend else 0,
            sliding_window=16 if self.sliding_window else None,
            attn_every=2 if self.attn_every else 0,
            dtype="float32",
            remat="none",
            param_sharding="tp",
        )
        if self.moe is not None:
            kw["moe"] = dataclasses.replace(
                self.moe, n_experts=4, top_k=2, d_ff_expert=64, first_dense_layers=min(self.moe.first_dense_layers, 1)
            )
        if self.ssm is not None:
            kw["ssm"] = dataclasses.replace(
                self.ssm, d_state=16, chunk_size=16, n_ssm_heads=2, slstm_every=2
            )
        return self.replace(**kw)


@dataclass(frozen=True)
class ServingConfig:
    """Serving knobs of ``repro.launch.serve_gnn.GNNServer``, shared by
    every model family it serves (``GNNConfig``, ``UNetConfig``)."""

    # serving: padding-bucket autoscaling (repro.launch.serve_gnn). Active
    # when bucket_policy == "auto" or the server gets bucket_sizes="auto";
    # the ladder is then derived from the observed request-size histogram
    # (quantile refits) and grown on demand for oversize traffic, with the
    # compiled-program cache bounded by max_live_buckets (LRU eviction).
    bucket_policy: str = "static"      # "static" | "auto"
    max_live_buckets: int = 8          # compiled-program cache bound (auto)
    bucket_granularity: int = 64       # auto bucket sizes round UP to this
    bucket_quantiles: Tuple[float, ...] = (0.5, 0.9)  # refit ladder targets
    bucket_refit_every: int = 32       # submits between ladder refits
    bucket_hist_len: int = 1024        # request-size histogram window
    # sharded serving (shard_devices > 1): headroom multiplier on the
    # reference plan's per-shard level capacities, so statistically similar
    # requests fit one frozen ShardSpec (= one compiled shard_map program
    # per bucket size). The autoscaling ladder above applies unchanged to
    # sharded buckets: ShardSpecs are derived per bucket size on demand
    # (graphx.sharded.shard_spec_for), not frozen at server init.
    shard_pad_factor: float = 1.3
    # observability (repro.telemetry): the span tracer + host profiler
    # annotations are gated by `telemetry` (a disabled tracer is a no-op
    # object — zero-cost-when-off); `trace_dir` is where exports land
    # (trace.jsonl, trace_chrome.json, metrics.prom, metrics.json);
    # `profile_capture` additionally records a full jax.profiler trace
    # under <trace_dir>/jax_profile. CLI: --telemetry / --trace-dir.
    telemetry: bool = False
    trace_dir: str = ""
    profile_capture: bool = False
    # cold start (repro.ckpt.compile_cache / artifact): when set, JAX's
    # persistent compilation cache lives here — recompiles of previously
    # seen bucket/train programs are disk loads, not XLA compiles, across
    # process restarts, autoscaler ladder growth and LRU evict→rebuild.
    # CLI: --compile-cache on serve_gnn and train. Deploy artifacts
    # (GNNServer.save_artifact / from_artifact) go further and bundle
    # AOT-serialized executables so a restored server pays zero compiles.
    compile_cache_dir: str = ""
    # resilience (repro.resilience + launch/serve_gnn hardening):
    # - request_timeout_s: per-request serving deadline (0 = none); an
    #   expired request is dropped from the plan before device work and
    #   resolved as a timed-out Result.error. submit(..., timeout_s=)
    #   overrides per request.
    # - max_queue_depth / shed_policy: bounded admission control (0 =
    #   unbounded). "reject" resolves overflow submits immediately as
    #   Result.error + a rejected_overload stat; "block" makes submit()
    #   wait for queue space (backpressure to the producer).
    # - worker_max_restarts / worker_backoff_s: a crashed background
    #   worker errors out its pending requests and restarts with capped
    #   exponential backoff; beyond max restarts the server goes dead
    #   (every submit resolves to an error, nobody hangs).
    # - nonfinite_guard: serving scans harvested outputs per item
    #   (NaN/Inf -> Result.error + nonfinite_results stat); training
    #   skips the optimizer update on a nonfinite loss/grad step.
    request_timeout_s: float = 0.0
    max_queue_depth: int = 0
    shed_policy: str = "reject"        # "reject" | "block"
    worker_max_restarts: int = 3
    worker_backoff_s: float = 0.05
    worker_backoff_max_s: float = 2.0
    nonfinite_guard: bool = True


@dataclass(frozen=True)
class GNNConfig(ServingConfig):
    """MeshGraphNet / X-MeshGraphNet configuration (the paper's own model)."""

    name: str = "xmgn"
    family: str = "gnn"
    node_in: int = 24                  # 3 pos + 3 normal + 18 fourier (paper: 24)
    edge_in: int = 4                   # relative pos (3) + distance (1)
    node_out: int = 4                  # pressure + 3 wall-shear components
    hidden: int = 512
    n_mp_layers: int = 15              # message-passing layers == halo size
    mlp_layers: int = 2
    act: str = "silu"
    norm: str = "layernorm"            # per-partition-local (no batch stats!)
    k_neighbors: int = 6
    levels: Tuple[int, ...] = (500_000, 1_000_000, 2_000_000)  # paper's 3-level
    n_partitions: int = 21
    halo: int = 15                     # == n_mp_layers
    fourier_freqs: Tuple[float, ...] = (2.0, 4.0, 8.0)  # x pi
    graph_source: str = "host"     # training-graph construction: "host"
                                   # (cKDTree multi-scale build in
                                   # data/pipeline.py) or "graphx" (the
                                   # device-resident hash-grid pipeline
                                   # serving uses — mesh-free, same edge
                                   # union, no cKDTree in the build)
    agg_impl: str = "xla"          # processor scatter-add: "xla" (plain
                                   # segment_sum), "sorted" (device argsort
                                   # once per graph + segment_sum with
                                   # indices_are_sorted), "pallas" (sorted
                                   # block packing + one-hot-MXU kernel)
    keep_ckpts: int = 0            # training: retain the K newest periodic
                                   # step-tagged checkpoints; restore falls
                                   # back past a corrupt one (--keep-ckpts)
    # transient rollouts (repro.launch.rollout): autoregressive T-step
    # physics rollouts served prefill/insert/generate style. The state
    # integrator is applied per step on the denormalized prediction:
    # "direct" (state := pred, so T=1 == single-shot serving bit-for-bit)
    # or "residual" (state := state + pred, MGN-style delta dynamics).
    # rollout_state_feats feeds the normalized current state back into the
    # node encoder (node_in_eff = node_in + node_out); off by default so
    # existing checkpoints/params keep their shapes.
    rollout_state_feats: bool = False
    rollout_integrator: str = "direct"  # "direct" | "residual"
    rollout_slots: int = 8              # concurrent rollouts per bucket table
    rollout_steps_per_flush: int = 4    # lax.scan steps per generate() call
    rollout_timeout_s: float = 0.0      # per-rollout deadline (0 = none)
    noise_std: float = 0.0         # training: MGN-style input-noise std on
                                   # node features (0 = bitwise-off)
    remat: bool = True             # activation checkpointing (paper SV-D)
    dtype: str = "float32"
    source: str = "arXiv X-MeshGraphNet (NVIDIA 2024)"

    @property
    def node_in_eff(self) -> int:
        """Node-encoder input width: static features (+ state when fed back)."""
        return self.node_in + (self.node_out if self.rollout_state_feats else 0)

    def replace(self, **kw) -> "GNNConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "GNNConfig":
        return self.replace(hidden=64, n_mp_layers=3, halo=3,
                            levels=(128, 256, 512), n_partitions=4)


@dataclass(frozen=True)
class UNetConfig(ServingConfig):
    """X-UNet3D (arXiv:2411.17164 §VI): 3D UNet with attention gates + halo
    partitioning, served as volume fields by ``GNNServer``.

    The voxel grid is ``grid`` voxels of ``voxel`` metres from ``box_lo``;
    its input features come from the request's sampled surface cloud
    (``repro.graphx.volume``): each voxel's nearest sample within
    ``sdf_band`` voxels, else its nearest among the cloud's first
    ``sdf_prefix`` samples, gives the signed distance and its gradient.
    Served, the grid is cut into ``n_partitions`` equal x-slabs, each run
    with ``halo`` planes of context on either side (``core.unet_halo``).
    """

    name: str = "xunet3d"
    family: str = "unet"
    in_channels: int = 16              # coords + fourier + sdf + sdf grads
    out_channels: int = 4              # velocity (3) + pressure
    base_channels: int = 64
    depth: int = 3
    blocks_per_level: int = 2
    kernel_size: int = 3
    pool: int = 2
    act: str = "gelu"
    attention_gates: bool = True
    halo: int = 40
    n_partitions: int = 10
    grid: Tuple[int, int, int] = (800, 304, 224)   # bbox / 1.5cm voxels
    voxel: float = 0.015               # voxel edge, metres
    box_lo: Tuple[float, float, float] = (-3.5, -2.28, -1.0)  # grid corner
    sdf_band: int = 3                  # exact near-surface search radius,
                                       # voxels
    sdf_prefix: int = 2048             # far-field fallback: nearest of the
                                       # cloud's first sdf_prefix samples
    dtype: str = "float32"
    source: str = "arXiv:2411.17164 (X-MeshGraphNet), Sec. VI: X-UNet3D"

    def replace(self, **kw) -> "UNetConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "UNetConfig":
        return self.replace(base_channels=8, depth=2, grid=(32, 16, 16),
                            halo=8, n_partitions=2, voxel=0.25,
                            box_lo=(-3.0, -2.0, -1.0), sdf_band=2,
                            sdf_prefix=64)


@dataclass(frozen=True)
class ShapeConfig:
    """An assigned input shape."""

    name: str
    seq_len: int
    global_batch: int
    kind: str                          # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


# TPU v5e-class hardware constants used by the roofline analysis.
@dataclass(frozen=True)
class HardwareSpec:
    peak_flops: float = 197e12         # bf16 FLOP/s per chip
    hbm_bw: float = 819e9              # bytes/s per chip
    ici_bw: float = 50e9               # bytes/s per link


HW = HardwareSpec()
