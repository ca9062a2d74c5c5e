"""Config system: typed dataclasses, a registry, and CLI overrides.

Every selectable architecture registers a ``ModelConfig`` factory under an id
(``--arch <id>``). Configs are plain frozen dataclasses so they hash and can be
closed over by jit without retracing surprises.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Model configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block config (DeepSeek-style fine-grained MoE)."""

    num_experts: int = 0          # routed experts
    num_shared: int = 0           # always-on shared experts
    top_k: int = 0
    expert_ff: int = 0            # per-expert hidden size
    router_aux_weight: float = 0.001
    # layers [first_moe_layer, num_layers) are MoE; earlier layers are dense
    first_moe_layer: int = 1
    dense_ff: int = 0             # ff size of the dense (non-MoE) layers
    capacity_factor: float = 1.25  # per-expert token capacity multiplier


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2)."""

    kv_lora_rank: int = 512
    q_lora_rank: int = 0          # 0 = full-rank q projection
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 SSD block config."""

    state_dim: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk: int = 256              # SSD chunk length


@dataclass(frozen=True)
class RGLRUConfig:
    """RecurrentGemma RG-LRU block config."""

    lru_width: int = 0            # 0 -> d_model
    conv_width: int = 4
    block_pattern: Tuple[str, ...] = ("recurrent", "recurrent", "attention")


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"         # dense | moe | ssm | hybrid | vlm | audio | lartpc
    num_layers: int = 2
    d_model: int = 128
    num_heads: int = 2
    num_kv_heads: int = 2
    head_dim: int = 0             # 0 -> d_model // num_heads
    d_ff: int = 256
    vocab_size: int = 1000
    max_seq_len: int = 8192
    # attention details
    attn_kind: str = "global"     # global | local | local_global | none
    window_size: int = 4096       # for local attention
    qk_norm: bool = False
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    rope_theta: float = 10000.0
    # mlp
    mlp_kind: str = "swiglu"      # swiglu | squared_relu | gelu | relu
    # norm / embeddings
    norm_kind: str = "rmsnorm"    # rmsnorm | layernorm
    tie_embeddings: bool = False
    embedding_scale: bool = False  # gemma-style sqrt(d_model) input scaling
    # sub-configs
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    # enc-dec
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    # multimodal stub frontends: number of precomputed embedding positions
    frontend: str = "none"        # none | vision | speech
    frontend_tokens: int = 0
    # numerics
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # remat: none | full | selective
    remat: str = "selective"

    #: embedding/unembedding tables are padded to a multiple of this so the
    #: vocab dim shards cleanly over the model axis (Megatron convention)
    vocab_pad_to: int = 256

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_to
        return (self.vocab_size + m - 1) // m * m

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    def param_count(self) -> int:
        """Approximate parameter count (used for 6ND model flops)."""
        from repro.models.model import count_params_analytic

        return count_params_analytic(self)

    def active_param_count(self) -> int:
        from repro.models.model import count_params_analytic

        return count_params_analytic(self, active_only=True)


# ---------------------------------------------------------------------------
# LArTPC sim config (the paper's own workload)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LArTPCConfig:
    name: str = "lartpc_uboone"
    family: str = "lartpc"
    # readout grid (paper: ~10k x 10k)
    num_wires: int = 2560          # one plane of MicroBooNE-like detector
    num_ticks: int = 9592          # readout window, 0.5 us ticks
    # depos
    num_depos: int = 100_000       # paper benchmarks 100k depos
    patch_wires: int = 20          # paper: ~20x20 patches
    patch_ticks: int = 20
    # padded (TPU-tile aligned) patch shape used by kernels
    pad_wires: int = 24
    pad_ticks: int = 128
    # physics-ish constants (arbitrary but shaped like the real thing)
    wire_pitch_mm: float = 3.0
    tick_us: float = 0.5
    drift_speed_mm_us: float = 1.6
    diffusion_long: float = 6.4    # mm^2/us-ish scaled
    diffusion_tran: float = 9.8
    # drift-stage diffusion shaping: width = sqrt(2 D t_drift) / metric
    #   * diffusion_scale + floor (floors keep patches resolvable; scale
    #   maps the synthetic diffusion constants onto patch-sized widths)
    diffusion_scale: float = 1e-2
    sigma_w_floor: float = 0.6     # wire units
    sigma_t_floor: float = 0.8     # tick units
    # drift-stage charge physics; defaults reproduce the seed behavior
    # (no attenuation, unit recombination survival)
    electron_lifetime_us: float = 0.0   # 0 disables lifetime attenuation
    recombination: float = 1.0          # flat recombination survival factor
    # jnp: vectorized transport; auto: resolve via the strategy registry
    drift_strategy: str = "jnp"
    nsigma: float = 3.0
    # electrons per depo (mean), fluctuation model
    electrons_per_depo: float = 5000.0
    fluctuate: bool = True
    # counter : threefry counter RNG, normal approximation (TPU-native)
    # pool    : paper-faithful pre-computed normal pool
    # relaxed : the counter draw with NaN-free reverse-mode gradients —
    #           value-identical forward (bit-for-bit with "counter"), but
    #           the zero-variance sqrt is reparameterized so jax.grad of
    #           the pipeline is finite (see docs/calibration.md)
    # none    : no fluctuation
    rng_strategy: str = "counter"  # counter | pool | relaxed | none
    # xla: one scatter HLO with a (pw, pt) window per depo (the CPU's
    #   default; the TPU compiler turns it into one loop trip per depo);
    # lane_rows: patch rows placed in 128-tick rows and added by one row
    #   scatter per chunk, which the TPU sorts and applies natively (the
    #   TPU's default);
    # sort_segment: sorted sequential-traffic form;
    # pallas: owner-computes tile kernel (dense tile grid);
    # pallas_compact: owner-computes over OCCUPIED tiles only;
    # auto: resolve via the kernel-strategy registry / tuning cache
    # (repro.tune — see docs/tuning.md)
    scatter_strategy: str = "xla"
    # unfused: rasterize -> fluctuate -> scatter_add;
    # unfused_bf16: same chain with bfloat16 patches (half the HBM traffic);
    # fused_pallas: single rasterize+fluctuate+scatter kernel (in-kernel RNG);
    # fused_pallas_compact: fused kernel over occupied tiles only; auto
    charge_grid_strategy: str = "unfused"
    # patch array dtype between rasterize and scatter ("float32" |
    # "bfloat16"): bf16 halves the (N, pw, pt) HBM traffic; accumulation
    # into the readout grid always happens in float32
    patch_dtype: str = "float32"
    # rfft2 | fft2 | auto — frequency-domain convolution layout
    fft_strategy: str = "rfft2"
    pipeline: str = "fig4"         # fig3 | fig4
    # response
    response_ticks: int = 200
    response_wires: int = 21       # +-10 wires induction span
    # overall response amplitude (dimensionless gain on the normalized
    # kernel) and electronics shaping time [us] — exposed as config fields
    # so gradient-based calibration (docs/calibration.md) can fit them; the
    # defaults reproduce the previous hard-coded response bit-for-bit
    response_gain: float = 1.0
    response_shaping_us: float = 2.0
    noise_rms_adc: float = 1.2
    adc_per_electron: float = 0.01
    adc_baseline: float = 900.0
    # straight-through estimator for the digitize round/clip: forward values
    # are UNCHANGED (round-then-clip and clip-then-round agree for integer
    # rails) but the output stays float32 and gradients pass straight
    # through inside the ADC rails (zero outside). Default False keeps the
    # int16 seed path bit-identical; the fit driver flips it on.
    digitize_ste: bool = False
    dtype: str = "float32"
    # ---- multi-plane readout geometry (ISSUE 5 tentpole) ----
    # number of wire planes read out per event. 1 (the default) is the seed
    # single-plane readout, bit-identical to every pre-multi-plane revision;
    # 3 is the paper-faithful MicroBooNE-like U/V/W triple (two induction
    # planes at +-60 degrees, one vertical collection plane). The per-plane
    # tuples below describe the full triple and are consumed as the first
    # ``num_planes`` entries when ``num_planes > 1`` (see ``plane_specs``).
    num_planes: int = 1
    # wire ANGLE per plane, degrees from vertical; the pitch direction the
    # ``wire`` coordinate indexes is perpendicular to the wires
    plane_angles_deg: Tuple[float, ...] = (60.0, -60.0, 0.0)
    # per-plane wire pitch [mm]; () means ``wire_pitch_mm`` for every plane
    plane_pitches_mm: Tuple[float, ...] = ()
    # per-plane field-response type: "induction" (bipolar) | "collection"
    # (unipolar) — selects the plane's ``make_response`` kernel
    plane_types: Tuple[str, ...] = ("induction", "induction", "collection")
    # per-plane wire count; () means ``num_wires`` on every plane, each
    # output leaf then carrying a plane axis (P, num_wires, num_ticks).
    # Given, the planes stack along ONE wire axis in plane order, each at
    # its own count: (sum of plane_wires, num_ticks), hits (P, max_hits),
    # over the face those wires cover (``face_extent_mm``)
    plane_wires: Tuple[int, ...] = ()
    # how the plane axis is dispatched when ``num_planes > 1`` (ISSUE 9):
    #   loop    : the original static Python loop — P charge-grid/convolve/
    #             noise programs and (distributed) P collectives per step
    #   stacked : one batched dispatch over a real (P, ...) array axis —
    #             plane-vmapped charge grid, one batched rfft2 with stacked
    #             per-plane response spectra, one batched noise draw, and a
    #             single reduce-scatter / all_to_all in the distributed
    #             executor. Bit-identical to "loop" (same per-plane
    #             fold_in subkeys)
    #   auto    : "stacked" for multi-plane configs, "loop" otherwise
    plane_batching: str = "auto"
    # ---- sim -> recon loop (ISSUE 6): deconvolution + hit finding ----
    # frequency-domain filter applied with the inverse response:
    #   wiener   : conj(R) / (|R|^2 + lambda * max|R|^2) — optimal-ish
    #              inversion with bounded gain where |R| is small
    #   gaussian : the same bounded inversion times a Gaussian low-pass
    #              along the time-frequency axis (DC gain exactly 1)
    deconv_filter: str = "wiener"
    # Wiener regularizer, as a fraction of max |R|^2 over the spectrum;
    # bounds the filter gain at 1 / (2 sqrt(lambda * max|R|^2))
    deconv_wiener_lambda: float = 2e-3
    # Gaussian low-pass cutoff, as a fraction of the time-axis Nyquist
    deconv_gauss_cut: float = 0.25
    # rfft2: direct half-spectrum inversion; fft_reuse: dispatch through the
    # tuned fft_convolve machinery (inverse filter as a DetectorResponse);
    # auto: tuning cache / backend default (plane-keyed, like fft_strategy)
    deconv_strategy: str = "rfft2"
    # scan: vectorized lax.scan threshold ROI finder (XLA); pallas: per-wire
    # Pallas scan kernel; auto (default): resolve via the strategy registry /
    # tuning cache — both strategies share one ROI-scan body, so the choice
    # is a pure perf decision (bit-identical outputs either way)
    hitfind_strategy: str = "auto"
    # hit threshold on the deconvolved charge, electrons per pixel; runs of
    # consecutive above-threshold ticks on one wire become hits
    hit_threshold: float = 500.0
    # HitSet capacity per plane (mask-padded, fixed shape for jit/vmap)
    max_hits: int = 4096
    # per-wire ROI capacity before compaction into the global HitSet
    max_hits_per_wire: int = 8
    # ---- fault tolerance (ISSUE 8): in-graph numeric sentinel ----
    # True wraps every float-producing stage with a jit-cheap
    # ``jnp.isfinite`` reduction, AND-ed into a ``finite_ok`` output flag
    # (per event under vmap) so the streaming layer can count events whose
    # pipeline went NaN/Inf mid-flight. Off (the default) adds NOTHING to
    # the traced program — bit-identical output (docs/robustness.md)
    check_finite: bool = False


class PlaneSpec(NamedTuple):
    """Resolved geometry of one readout plane (plain data, hashable)."""

    index: int
    kind: str          # "induction" | "collection"
    angle_deg: float   # wire angle from vertical, degrees
    pitch_mm: float    # wire pitch of this plane
    num_wires: int     # wires of this plane


def plane_specs(cfg: "LArTPCConfig") -> Tuple[PlaneSpec, ...]:
    """Resolved per-plane geometry of ``cfg``.

    ``num_planes == 1`` is the seed single-plane readout: identity
    projection (wires perpendicular to the generator's transverse axis,
    angle 0, pitch ``wire_pitch_mm``) with the bipolar induction response —
    the exact pre-multi-plane behavior, so the plane tuples are not
    consulted. ``num_planes > 1`` reads the first ``num_planes`` entries of
    ``plane_angles_deg`` / ``plane_pitches_mm`` / ``plane_types`` /
    ``plane_wires``.
    """
    if cfg.num_planes < 1:
        raise ValueError(f"num_planes must be >= 1, got {cfg.num_planes}")
    if cfg.num_planes == 1:
        return (PlaneSpec(0, "induction", 0.0, cfg.wire_pitch_mm,
                          cfg.num_wires),)
    pitches = cfg.plane_pitches_mm or (cfg.wire_pitch_mm,) * cfg.num_planes
    wires = cfg.plane_wires or (cfg.num_wires,) * cfg.num_planes
    for name, tup in (("plane_angles_deg", cfg.plane_angles_deg),
                      ("plane_pitches_mm", pitches),
                      ("plane_types", cfg.plane_types),
                      ("plane_wires", wires)):
        if len(tup) < cfg.num_planes:
            raise ValueError(
                f"{name} has {len(tup)} entries < num_planes={cfg.num_planes}")
    for kind in cfg.plane_types[: cfg.num_planes]:
        if kind not in ("induction", "collection"):
            raise ValueError(f"unknown plane type {kind!r}; expected "
                             "'induction' or 'collection'")
    return tuple(
        PlaneSpec(p, cfg.plane_types[p], cfg.plane_angles_deg[p], pitches[p],
                  wires[p])
        for p in range(cfg.num_planes))


def concat_planes(cfg: "LArTPCConfig") -> bool:
    """True when the planes stack along one wire axis: a multi-plane
    readout given per-plane wire counts (``plane_wires``)."""
    return cfg.num_planes > 1 and bool(cfg.plane_wires)


def face_extent_mm(cfg: "LArTPCConfig") -> Tuple[float, float]:
    """(y, z) extent in mm of the face the generator draws over and the
    planes are centred on; y runs along the 0-degree plane's pitch, z along
    its wires.

    Planes of their own wire counts (``concat_planes``) read the face their
    wires cover exactly: y spans the 0-degree plane's wires, and z is the
    height at which every angled plane's wires span the face's projection,
    (wires pitch - y |cos a|) / |sin a| (the least over the angled planes).
    Otherwise it is the seed box of ``num_wires`` wires, y in
    [0, (num_wires - 1) pitch], z in [0, num_wires pitch].
    """
    import math

    if not concat_planes(cfg):
        return ((cfg.num_wires - 1.0) * cfg.wire_pitch_mm,
                cfg.num_wires * cfg.wire_pitch_mm)
    specs = plane_specs(cfg)
    straight = [s for s in specs if s.angle_deg == 0.0]
    angled = [s for s in specs if s.angle_deg != 0.0]
    if not straight or not angled:
        raise ValueError(
            "planes of their own wire counts (plane_wires) need a 0-degree "
            "plane, whose wires span the face, and an angled one, whose "
            f"wires fix its height; got angles {[s.angle_deg for s in specs]}")
    y = straight[0].num_wires * straight[0].pitch_mm
    z = min((s.num_wires * s.pitch_mm
             - y * abs(math.cos(math.radians(s.angle_deg))))
            / abs(math.sin(math.radians(s.angle_deg))) for s in angled)
    if z <= 0.0:
        raise ValueError(
            f"the angled planes' wires do not span the {y} mm the 0-degree "
            "plane's wires cover (plane_wires)")
    return y, z


# ---------------------------------------------------------------------------
# Shapes (assigned input-shape set)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str                     # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}


# ---------------------------------------------------------------------------
# Run/training config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParallelConfig:
    data_axis: str = "data"
    model_axis: str = "model"
    pod_axis: str = "pod"
    fsdp: bool = True              # shard params over data axis
    expert_axis: str = "model"     # EP placement
    sequence_parallel: bool = False
    grad_compression: str = "none"  # none | int8_ef
    microbatches: int = 1
    remat_policy: str = "selective"


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    schedule: str = "cosine"       # cosine | linear | constant


@dataclass(frozen=True)
class CheckpointConfig:
    directory: str = "/tmp/repro_ckpt"
    every_steps: int = 50
    keep: int = 3
    async_save: bool = True


@dataclass(frozen=True)
class TrainConfig:
    model: Any = None
    shape: ShapeConfig = SHAPES["train_4k"]
    parallel: ParallelConfig = ParallelConfig()
    optimizer: OptimizerConfig = OptimizerConfig()
    checkpoint: CheckpointConfig = CheckpointConfig()
    seed: int = 0
    log_every: int = 10
    straggler_deadline_s: float = 0.0   # 0 disables


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[[], Any]] = {}
_SMOKE_REGISTRY: Dict[str, Callable[[], Any]] = {}


def register(arch_id: str, full: Callable[[], Any], smoke: Callable[[], Any]) -> None:
    _REGISTRY[arch_id] = full
    _SMOKE_REGISTRY[arch_id] = smoke


def get_config(arch_id: str, smoke: bool = False):
    import repro.configs  # noqa: F401  (triggers registration)

    table = _SMOKE_REGISTRY if smoke else _REGISTRY
    if arch_id not in table:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(table)}")
    return table[arch_id]()


def list_archs() -> Sequence[str]:
    import repro.configs  # noqa: F401

    return sorted(_REGISTRY)


def apply_overrides(cfg, overrides: Dict[str, Any]):
    """dot.path=value overrides onto nested frozen dataclasses."""
    for key, value in overrides.items():
        parts = key.split(".")
        cfg = _apply_one(cfg, parts, value)
    return cfg


def _apply_one(cfg, parts, value):
    if len(parts) == 1:
        fld = {f.name: f for f in dataclasses.fields(cfg)}[parts[0]]
        typ = fld.type
        if isinstance(value, str):
            if typ in ("int", int):
                value = int(value)
            elif typ in ("float", float):
                value = float(value)
            elif typ in ("bool", bool):
                value = value.lower() in ("1", "true", "yes")
        elif isinstance(value, list):
            # a JSON list: the frozen config hashes, so a list field would
            # not (jit keys on the config)
            value = tuple(value)
        return replace(cfg, **{parts[0]: value})
    sub = getattr(cfg, parts[0])
    return replace(cfg, **{parts[0]: _apply_one(sub, parts[1:], value)})
