"""Config dataclasses for the model zoo, federated runtime and input shapes."""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

VOCAB_PAD_MULTIPLE = 256


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared: int = 0
    d_ff_shared: int = 0
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2)."""
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 -> d_model // num_heads
    # layer stacking: pattern of block kinds, tiled over num_layers.
    #   attn | local | global | rec (RG-LRU) | m (mLSTM) | s (sLSTM)
    block_pattern: Tuple[str, ...] = ("attn",)
    # attention options
    causal: bool = True
    qk_norm: bool = False
    softcap_attn: Optional[float] = None
    softcap_final: Optional[float] = None
    window: Optional[int] = None      # sliding-window size for 'local' blocks
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0           # chatglm applies rotary to half the dims
    mrope_sections: Optional[Tuple[int, int, int]] = None  # qwen2-vl M-RoPE
    mla: Optional[MLAConfig] = None
    # ffn
    ffn_kind: str = "swiglu"          # swiglu | geglu | gelu
    moe: Optional[MoEConfig] = None
    # recurrent blocks
    lru_width: int = 0                # RG-LRU recurrence width (0 -> d_model)
    conv_width: int = 4               # temporal conv in recurrent blocks
    mlstm_proj_factor: float = 2.0
    slstm_proj_factor: float = 4.0 / 3.0
    # perf knobs (§Perf hillclimb; defaults = paper-faithful baseline)
    pad_attn_heads: int = 0           # pad q-heads to this count with zero
    # wq cols / wo rows (mathematically exact — zero heads contribute 0 and
    # receive 0 gradient). Aligns num_heads to the model axis so attention
    # shards on heads instead of splitting head_dim (which turns every
    # score einsum into a partial-sum all-reduce).
    slstm_unroll: int = 1             # scan unroll: weights read once/U steps
    attn_chunk_threshold: int = 2048  # seq len above which attention uses
    # the online-softmax KV-chunked path (0 = always chunked; big = dense)
    attn_kv_chunk: int = 1024         # KV tile for the chunked path
    train_remat: bool = True          # per-block activation checkpointing
    scan_compute_dtype: str = "float32"   # mLSTM chunk-scan operand dtype:
    #   "bfloat16" keeps q/k/v bf16 across the sharding boundary (halves the
    #   per-chunk model-axis all-gather bytes); accumulation stays fp32.
    # misc
    residual_scale: float = 1.0       # minicpm depth scaling
    scale_emb: float = 1.0
    tie_embeddings: bool = True
    post_norm: bool = False           # gemma2 post-block norms
    dtype: str = "float32"
    # serving: replace 'global' with 'local' blocks for long-context mode
    long_mode_swa_only: bool = False
    # frontend stubs (audio/vlm): inputs are embeddings, not token ids
    embedding_inputs: bool = False

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def vocab_padded(self) -> int:
        m = VOCAB_PAD_MULTIPLE
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def pattern_reps(self) -> int:
        return self.num_layers // len(self.block_pattern)

    @property
    def pattern_remainder(self) -> Tuple[str, ...]:
        rem = self.num_layers % len(self.block_pattern)
        return tuple(self.block_pattern[:rem])

    def with_depth(self, num_layers: int) -> "ModelConfig":
        """This config at ``num_layers`` layers, rounded down to whole
        ``block_pattern`` periods (at least one), every width kept."""
        period = len(self.block_pattern)
        num_layers = max(num_layers, period) // period * period
        return dataclasses.replace(self, num_layers=num_layers)

    def reduced(self, num_layers: int = 2, d_model: int = 256,
                max_experts: int = 4) -> "ModelConfig":
        """Smoke-test variant: same family/features, tiny dims."""
        num_layers = self.with_depth(num_layers).num_layers
        heads = min(self.num_heads, 4)
        kv = min(self.num_kv_heads, heads)
        if heads % kv:
            kv = 1
        changes = dict(
            num_layers=num_layers, d_model=d_model, num_heads=heads,
            num_kv_heads=kv, head_dim=d_model // heads,
            d_ff=max(2 * d_model, 64), vocab_size=min(self.vocab_size, 512),
            lru_width=min(self.lru_width, d_model) if self.lru_width else 0,
            window=min(self.window, 64) if self.window else None,
        )
        if self.moe is not None:
            changes["moe"] = dataclasses.replace(
                self.moe, num_experts=min(self.moe.num_experts, max_experts),
                top_k=min(self.moe.top_k, 2),
                d_ff_expert=max(d_model // 2, 32),
                num_shared=min(self.moe.num_shared, 1),
                d_ff_shared=max(d_model // 2, 32) if self.moe.num_shared else 0)
        if self.mla is not None:
            changes["mla"] = MLAConfig(kv_lora_rank=64, qk_nope_head_dim=32,
                                       qk_rope_head_dim=16, v_head_dim=32)
            changes["head_dim"] = 32
        if self.mrope_sections is not None:
            hd = changes["head_dim"]
            changes["mrope_sections"] = (hd // 2 - 2 * (hd // 8), hd // 8, hd // 8)
        return dataclasses.replace(self, **changes)


#: The named wire streams of a federated round (see docs/wire-format.md).
#: Every stream shares the packed (rows, cols) layout of `repro.comm.flat`
#: and gets its own compressor + error-feedback policy via
#: `CommConfig.stream(name)`.
COMM_STREAMS = ("uplink", "downlink", "hessian")


@dataclass(frozen=True)
class CommConfig:
    """Client<->server communication model (repro.comm).

    The round is modelled as three named wire streams, each with an
    independent compressor (``COMM_STREAMS``):

    * ``uplink`` — the client *param-delta* (theta_i - theta_i^rx after
      local training), compressed per participant with optional
      client-side error feedback.
    * ``downlink`` — the server broadcast, as a per-client delta
      against each client's last-received model, with server-side
      per-client error feedback (``downlink_*`` fields).
    * ``hessian`` — the Hessian-EMA (Sophia ``h``) uplink plus the
      common averaged-curvature broadcast back (``hessian_*`` fields;
      ``"off"`` disables the stream entirely).

    The default — lossless identity uplink/downlink, hessian off, full
    participation — makes the round bit-identical to the direct
    client-mean path, so existing runs are untouched; any other setting
    routes the round through the delta-space
    encode/aggregate/broadcast pipeline in `FedEngine`.
    """
    compressor: str = "identity"      # identity | int8 | int4 | topk | signsgd
    # Per-client error-feedback residual (EF-SGD). "auto" materialises
    # it exactly for the biased compressors (topk, signsgd) that need it
    # to converge; True forces it for any lossy compressor (C full fp32
    # model copies of HBM); False disables it.
    error_feedback: object = "auto"   # "auto" | True | False
    participation: float = 1.0        # fraction S/C of clients sampled/round
    topk_ratio: float = 0.01          # k = ceil(ratio * n_params)
    sign_majority: bool = False       # signsgd: server majority vote on signs
    quant_block: int = 1024           # elements per quantization scale group
    use_pallas: bool = False          # fused quantize/dequantize kernels
    seed: int = 0                     # participation-sampling salt
    # ---- downlink stream (server -> client broadcast) -----------------
    # "identity" keeps the PR-1 exact fp32 broadcast (no per-client
    # model replicas allocated); any other value compresses the
    # broadcast as a delta vs each client's last-received model, with
    # server-side per-client error feedback.
    downlink_compressor: str = "identity"
    downlink_error_feedback: object = "auto"   # "auto" | True | False
    # ---- hessian stream (Sophia h-EMA uplink + averaged broadcast) ----
    # "off" disables the stream (no curvature crosses the wire). Any
    # compressor name enables curvature averaging: participants upload
    # their compressed h-EMA, the server averages and broadcasts ONE
    # common payload back. Second-order state is smoother than
    # gradients, so the intended default when enabled is "int4".
    hessian_compressor: str = "off"
    # ---- resident-state storage dtype ---------------------------------
    # Storage dtype of the wire-layout state that LIVES on device
    # between rounds (packed params, the (C, rows, cols) Sophia m/h
    # EMAs, EF residuals, downlink replicas). "bfloat16" halves the
    # resident-state HBM; every round still computes in fp32 — rows are
    # upcast when gathered and downcast when scattered back, and the
    # fused Pallas kernels carry a dtype-parameterized load/store path.
    # Wire payloads are unaffected (bytes on the wire follow the
    # compressor, not this dtype).
    state_dtype: str = "float32"      # float32 | bfloat16 | float8_e4m3fn | float8_e5m2
    # Per-buffer overrides of state_dtype for the two largest resident
    # stacks, the (C, rows, cols) Sophia EMAs: moment_dtype stores m,
    # hessian_dtype stores h. "" inherits state_dtype. The fp8 formats
    # (float8_e4m3fn for m — more mantissa; float8_e5m2 for h — more
    # range) cut the dominant resident-state HBM to 0.25x of fp32;
    # compute still upcasts to fp32 in-kernel, so only one store
    # rounding per round is added per buffer.
    moment_dtype: str = ""            # "" -> inherit state_dtype
    hessian_dtype: str = ""           # "" -> inherit state_dtype
    # ---- per-stream packing geometry overrides (0/0.0 = inherit) ------
    # Each stream may override the quantization group size and top-k
    # sparsity of its packed layout: curvature is much smoother than
    # gradients, so the hessian stream typically affords coarser groups
    # (fewer fp32 scales on the wire).  The stream's (rows, cols) wire
    # layout follows its own quant_block, so streams may disagree on
    # geometry; they always share the flattened `total` coordinates.
    downlink_quant_block: int = 0     # 0 -> inherit quant_block
    downlink_topk_ratio: float = 0.0  # 0.0 -> inherit topk_ratio
    hessian_quant_block: int = 0      # 0 -> inherit quant_block
    hessian_topk_ratio: float = 0.0   # 0.0 -> inherit topk_ratio

    @property
    def lossless(self) -> bool:
        return self.compressor == "identity"

    @property
    def downlink_enabled(self) -> bool:
        return self.downlink_compressor != "identity"

    @property
    def hessian_enabled(self) -> bool:
        return self.hessian_compressor != "off"

    @property
    def multi_stream(self) -> bool:
        """Any stream beyond the PR-1 uplink is active."""
        return self.downlink_enabled or self.hessian_enabled

    def stream(self, name: str) -> "CommConfig":
        """Per-stream view: this config with ``compressor`` /
        ``error_feedback`` / packing geometry (``quant_block``,
        ``topk_ratio``) resolved for the named stream, so the same
        compressor factory and accounting serve every stream."""
        if name == "uplink":
            return self
        if name == "downlink":
            return dataclasses.replace(
                self, compressor=self.downlink_compressor,
                error_feedback=self.downlink_error_feedback,
                quant_block=self.downlink_quant_block or self.quant_block,
                topk_ratio=self.downlink_topk_ratio or self.topk_ratio)
        if name == "hessian":
            c = self.hessian_compressor
            return dataclasses.replace(
                self, compressor="identity" if c == "off" else c,
                error_feedback=False,
                quant_block=self.hessian_quant_block or self.quant_block,
                topk_ratio=self.hessian_topk_ratio or self.topk_ratio)
        raise ValueError(f"unknown stream {name!r} (want {COMM_STREAMS})")

    def num_participants(self, num_clients: int) -> int:
        s = int(round(self.participation * num_clients))
        return max(1, min(num_clients, s))


#: Round disciplines of the virtual-time scheduler (repro.sched).
SCHED_DISCIPLINES = ("sync", "semisync", "async")

#: Latency profiles of the virtual-time scheduler (repro.sched).
LATENCY_PROFILES = ("uniform", "straggler", "lognormal")


@dataclass(frozen=True)
class SchedConfig:
    """Virtual-time round scheduling (repro.sched).

    A deterministic event simulator assigns every client a latency
    (compute seconds per local step plus transfer seconds derived from
    the comm layer's exact per-stream byte counts and ``bandwidth_bps``)
    and drives one of three round disciplines:

    * ``sync`` — today's engine behaviour, bit-exact: every sampled
      client trains each round, the round takes as long as its slowest
      participant.
    * ``semisync`` — FedBuff-style: the server aggregates the first
      ``buffer_size`` arrivals of each round (staleness-weighted mean);
      stragglers keep training and deliver stale deltas into a later
      buffer.
    * ``async`` — every arrival is applied immediately with the
      staleness-decayed weight ``(1 + staleness)^-staleness_power``.
    """
    discipline: str = "sync"          # sync | semisync | async
    buffer_size: int = 0              # semisync: arrivals per aggregation
    #                                   (0 -> all in-flight participants)
    staleness_power: float = 0.5      # arrival weight (1+tau)^-p
    latency_profile: str = "uniform"  # uniform | straggler | lognormal
    compute_s: float = 1.0            # base seconds per local iteration
    bandwidth_bps: float = 1e8        # base link speed, bits/second
    straggler_frac: float = 0.25      # straggler: fraction of slow clients
    straggler_slowdown: float = 10.0  # straggler: slow-client multiplier
    lognormal_sigma: float = 0.75     # lognormal: client-speed spread
    seed: int = 0                     # latency-sampling salt
    # Dispatch groups larger than this run as a lax-driven sequence of
    # fixed-size client chunks through the ONE-launch batched comm step
    # (autotuned per-chunk kernel geometry), instead of one giant
    # launch; 0 disables chunking. Chunking is bitwise-neutral: each
    # chunk computes exactly the per-client op sequence.
    dispatch_chunk: int = 0           # 0 -> unchunked


#: Robust server-side aggregators (repro.robust). "mean" is today's
#: weighted-mean path, byte-for-byte; the others are pluggable
#: replacements for the combination step over the (K, rows, cols)
#: arrival stack (see docs/robustness.md).
AGGREGATORS = ("mean", "trimmed_mean", "coordinate_median", "norm_clip")

#: Byzantine wire attacks of the fault-injection layer (repro.robust).
#: Each transforms a malicious client's packed uplink buffer after
#: encoding, preserving wire geometry and headers.
ATTACKS = ("none", "sign_flip", "scale", "random_wire")


@dataclass(frozen=True)
class RobustConfig:
    """Adversarial-fleet knobs (repro.robust).

    Three orthogonal groups:

    * **aggregation** — ``aggregator`` picks the server-side combiner
      for client contributions (``AGGREGATORS``). ``trimmed_mean``
      drops the ``trim_fraction`` per-coordinate extremes on each side
      before the weighted mean; ``coordinate_median`` is the maximal
      trim (mid-K survivors); ``norm_clip`` rescales each arrival to
      L2 norm at most ``clip_norm`` before the weighted mean.
    * **byzantine faults** — ``attack`` applied to the packed wire
      buffer of the ``attack_fraction`` lowest-indexed malicious
      clients (deterministic per ``seed``), plus label-noise clients.
    * **fleet churn** — dropout/rejoin events on the virtual clock:
      each dispatch drops with ``dropout_prob`` and rejoins (delivers
      late) after ``rejoin_delay_s`` virtual seconds.

    The default is degenerate by construction: ``aggregator="mean"``
    with no adversaries routes through today's weighted-mean path
    untouched (bitwise), as do ``trimmed_mean`` at trim 0 and
    ``norm_clip`` at clip 0 (see docs/robustness.md).
    """
    aggregator: str = "mean"          # mean | trimmed_mean | coordinate_median | norm_clip
    trim_fraction: float = 0.0        # per-side per-coordinate trim (trimmed_mean)
    clip_norm: float = 0.0            # max L2 norm per arrival (norm_clip; 0 = off)
    # ---- byzantine fault injection ------------------------------------
    attack: str = "none"              # none | sign_flip | scale | random_wire
    attack_fraction: float = 0.0      # fraction of clients byzantine
    attack_scale: float = 10.0        # multiplier for the "scale" attack
    label_noise_fraction: float = 0.0 # fraction of clients with noisy labels
    label_noise_rate: float = 0.5     # P(label resampled) for noisy clients
    # ---- dropout / rejoin on the virtual clock ------------------------
    dropout_prob: float = 0.0         # per-dispatch client dropout probability
    rejoin_delay_s: float = 0.0       # extra virtual seconds before a dropped
    #                                   client's update is delivered
    seed: int = 0                     # fault-injection salt

    @property
    def adversarial(self) -> bool:
        """Any fault injection active (attacks, label noise or churn)."""
        return ((self.attack != "none" and self.attack_fraction > 0.0)
                or self.label_noise_fraction > 0.0
                or self.dropout_prob > 0.0)


@dataclass(frozen=True)
class ObsConfig:
    """Structured telemetry (repro.obs).

    ``probes=True`` adds device-side Sophia health metrics — clip
    fraction of the Eq. 11 step, m/h EMA norms, h-EMA staleness and
    the cumulative GNB refresh count — to the round metrics, computed
    INSIDE the jitted round with no extra host syncs (requires
    ``optimizer="fed_sophia"`` with ``persistent_client_state``; the
    probed round is bitwise identical in state to the unprobed one).
    Sinks, the record schema and the run manifest live in `repro.obs`;
    see docs/observability.md for the metric catalogue.
    """
    probes: bool = False              # device-side Sophia health probes
    #                                   in the round metrics dict
    trace: bool = False               # per-dispatch trace contexts on
    #                                   the virtual clock (repro.obs.trace)
    flush_every: int = 10             # rounds between metric-buffer
    #                                   flushes (host syncs) in obs runs
    ring_capacity: int = 1024         # in-memory ring sink capacity


@dataclass(frozen=True)
class FedConfig:
    """Federated runtime configuration (Alg. 1 hyper-parameters)."""
    num_clients: int = 32
    local_iters: int = 10             # J
    optimizer: str = "fed_sophia"     # fed_sophia | fedavg | done | fedadam | fedyogi
    strategy: str = "parallel"        # parallel (vmap) | sequential (scan)
    lr: float = 3e-3                  # eta
    beta1: float = 0.9
    beta2: float = 0.95
    rho: float = 0.04                 # clip threshold
    eps: float = 1e-12
    weight_decay: float = 1e-4        # lambda
    tau: int = 10                     # hessian refresh period
    hessian_every_unit: str = "step"  # step | round (paper-literal)
    # Persistent per-client (m, h) across rounds (Alg. 1 line 2). False =
    # stateless local optimizer (re-init each round): the memory-feasible
    # variant for >=14B archs where C x |theta| x 2 states cannot fit HBM
    # (DESIGN.md section 4); tau then counts within-round steps.
    persistent_client_state: bool = True
    # server-side optimizer params (FedAdam/FedYogi)
    server_lr: float = 0.1
    server_beta1: float = 0.9
    server_beta2: float = 0.99
    server_eps: float = 1e-3
    # DONE baseline
    done_richardson_iters: int = 20
    done_damping: float = 10.0
    # gradient accumulation: split each local batch into N micro-batches
    # and average the grads (mathematically exact; bounds activation
    # memory — the §Perf HBM-fit lever for large per-client batches)
    grad_microbatches: int = 1
    # schedule: const | cosine | wsd
    schedule: str = "const"
    warmup_rounds: int = 0
    total_rounds: int = 100
    decay_frac: float = 0.1           # WSD decay tail fraction
    use_pallas: bool = False          # fused Sophia kernel (interpret on CPU)
    # client<->server communication model (compression, participation,
    # bytes-on-the-wire accounting) — see repro.comm
    comm: CommConfig = field(default_factory=CommConfig)
    # virtual-time round scheduling (latency model, async/semisync
    # disciplines, staleness weighting) — consumed by repro.sched, not
    # by the engine itself; the default is today's synchronous rounds
    sched: SchedConfig = field(default_factory=SchedConfig)
    # structured telemetry (record schema, sinks, Sophia health probes)
    # — see repro.obs and docs/observability.md; the default is fully
    # off (no probe ops in the traced round)
    obs: ObsConfig = field(default_factory=ObsConfig)
    # adversarial fleet: robust aggregation, byzantine fault injection
    # and client churn — see repro.robust and docs/robustness.md; the
    # default is degenerate (today's weighted-mean path, bitwise)
    robust: RobustConfig = field(default_factory=RobustConfig)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                         # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    fed: FedConfig = field(default_factory=FedConfig)
    seed: int = 0
