"""Roofline analysis from the compiled dry-run artifact.

Three terms, all in seconds, per (arch × shape × mesh):

    compute    = HLO_FLOPs / (chips * PEAK_FLOPS)
    memory     = HLO_bytes / (chips * HBM_BW)
    collective = collective_bytes / (chips * ICI_BW)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``.
collective_bytes is NOT in cost_analysis — we parse the optimized HLO text
and sum result-shape bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute op.

Hardware constants (TPU v5e): 197 TFLOP/s bf16/chip, 819 GB/s HBM/chip,
~50 GB/s/link ICI.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Per-chip accelerator roofline description.

    One reusable record instead of scattered module constants, so the
    roofline report, the derived-TPU benchmark models, and the
    ``repro.sim`` cost models all price work against the same hardware
    description (and alternative chips are a dataclass instance away).

    The dispatch/pipeline terms extend the classic three-roof model with
    the launch-cost constants the space-time paper's gains hinge on:
    merging R kernels into one super-kernel pays ``dispatch_overhead_s``
    once instead of R times.
    """

    name: str = "tpu_v5e"
    peak_flops: float = 197e12       # bf16 FLOP/s per chip
    hbm_bw: float = 819e9            # bytes/s per chip
    ici_bw: float = 50e9             # bytes/s per link (one direction)
    dispatch_overhead_s: float = 2e-6    # host launch cost per kernel
    context_switch_s: float = 5e-6       # time-sliced context swap cost
    mxu_dim: int = 128                   # systolic array tile edge
    mxu_freq_hz: float = 940e6

    def t_compute(self, flops: float) -> float:
        return flops / self.peak_flops

    def t_memory(self, bytes_moved: float) -> float:
        return bytes_moved / self.hbm_bw

    def t_collective(self, bytes_moved: float) -> float:
        return bytes_moved / self.ici_bw

    def pipe_fill_s(self) -> float:
        """Systolic pipeline fill paid once per distinct kernel launch."""
        return self.mxu_dim / self.mxu_freq_hz

    def scaled(self, factor: float, name: Optional[str] = None) -> "HardwareSpec":
        """A same-architecture chip at ``factor`` x this one's throughput
        (an older or down-binned generation): the compute/memory/ICI roofs
        scale, the per-launch overheads (dispatch, context switch, pipe
        fill) do NOT — which is exactly why slower chips lose *more* to
        time-sliced multiplexing and heterogeneous fleets need
        speed-aware routing (see ``repro.sim.fleet``)."""
        if factor <= 0.0:
            raise ValueError(f"scale factor must be > 0, got {factor}")
        return dataclasses.replace(
            self,
            name=name or f"{self.name}_x{factor:g}",
            peak_flops=self.peak_flops * factor,
            hbm_bw=self.hbm_bw * factor,
            ici_bw=self.ici_bw * factor,
        )

    def sliced(self, share: float, name: Optional[str] = None) -> "HardwareSpec":
        """A fractional spatial partition of this chip: ``share`` of the
        compute/memory/ICI roofs, full-price launch overheads.

        ``scaled`` generalized from per-replica derating (a whole slower
        chip) to per-partition slices of ONE chip: a tenant granted 25%
        of the spatial units sees 25% of every roof, but still pays the
        full ``dispatch_overhead_s`` and pipe fill per kernel launch —
        the fixed terms that give throughput-vs-share curves their knee
        (``repro.partition.knee``). Shares of co-located slices must sum
        to <= 1.0; ``repro.partition.shares.PartitionPlan`` owns that
        validation."""
        if not (0.0 < share <= 1.0):
            raise ValueError(
                f"partition share must be in (0, 1], got {share} "
                f"(a share is a fraction of one chip's spatial units)")
        return self.scaled(share, name=name or f"{self.name}@{share:g}")


TPU_V5E = HardwareSpec()

# Named chips for CLI/spec surfaces (``fleet_sweep --specs ...``,
# ``repro.api`` hardware names): the current generation plus derated
# older generations of the same architecture — launch overheads
# identical, roofs scaled (see ``HardwareSpec.scaled``). Lives beside
# ``HardwareSpec`` so every layer (roofline report, sim cost models,
# declarative SystemSpec) resolves names against ONE registry.
HARDWARE_SPECS: Dict[str, HardwareSpec] = {
    "v5e": TPU_V5E,
    "v5e_half": TPU_V5E.scaled(0.5, name="v5e_half"),
    "v5e_quarter": TPU_V5E.scaled(0.25, name="v5e_quarter"),
}


#: ``jax.Device.device_kind`` -> ``HARDWARE_SPECS`` name of that chip.
#: The CPU backend maps to None: it runs the live path for functional
#: tests only and has no roofline, so nothing it measures prices a chip.
DEVICE_KINDS: Dict[str, Optional[str]] = {
    "TPU v5 lite": "v5e",
    "cpu": None,
}


def hardware_for_device(device_kind: str) -> Optional[str]:
    """The ``HARDWARE_SPECS`` name of a device, from its ``device_kind``.

    A kind missing from ``DEVICE_KINDS`` raises: pricing an unknown chip
    with some other chip's roofline would pass a model off as the device.
    """
    try:
        return DEVICE_KINDS[device_kind]
    except KeyError:
        raise ValueError(
            f"unknown device kind {device_kind!r}: add it to "
            f"repro.launch.roofline.DEVICE_KINDS with its HARDWARE_SPECS "
            f"entry (known kinds: {sorted(DEVICE_KINDS)})") from None


def check_device_hardware(device_kind: str, hardware) -> None:
    """Raise unless ``hardware`` (a spec or name) describes the device
    kind. CPU devices pass: they carry no roofline to disagree with."""
    name = hardware_for_device(device_kind)
    spec = resolve_spec(hardware)
    if name is not None and HARDWARE_SPECS[name] != spec:
        raise ValueError(
            f"hardware spec {spec.name!r} does not describe the device "
            f"{device_kind!r} (that is {name!r}); price this fleet with "
            f"cost_model.hardware={name!r}")


def resolve_spec(spec) -> HardwareSpec:
    """Accept a ``HardwareSpec`` or a ``HARDWARE_SPECS`` name.

    Unknown names raise a ``ValueError`` that lists the registered keys —
    the same actionable message ``repro.api`` spec validation surfaces.
    """
    if isinstance(spec, HardwareSpec):
        return spec
    try:
        return HARDWARE_SPECS[spec]
    except (KeyError, TypeError):
        raise ValueError(
            f"unknown hardware spec {spec!r} "
            f"(names: {sorted(HARDWARE_SPECS)})") from None


# Backwards-compatible module constants (pre-HardwareSpec callers).
PEAK_FLOPS = TPU_V5E.peak_flops
HBM_BW = TPU_V5E.hbm_bw
ICI_BW = TPU_V5E.ici_bw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# one HLO instruction: `%name = <shape> opcode(...)` — shape may be a tuple.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],{}\/ ]+?)\s+([\w\-]+)(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes per collective kind over the whole module.

    '-start' variants are counted, '-done' skipped (same buffer). Sizes are
    the GLOBAL logical buffers in the annotated module; divide by chips for
    per-chip traffic downstream.
    """
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        shape_str, opcode = m.groups()
        for coll in _COLLECTIVES:
            if opcode == coll or opcode == coll + "-start":
                out[coll] += _shape_bytes(shape_str)
                break
    return out


COLL_FACTOR = {
    # per-chip ICI traffic multiplier on the op's LOCAL result bytes
    # (partitioned-module shapes): ring all-gather moves ~result bytes per
    # chip; ring all-reduce ~2x its buffer; the rest ~1x.
    "all-gather": 1.0,
    "all-reduce": 2.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float             # raw cost_analysis (CPU backend: while-body
    hlo_bytes: float             # counted once — recorded for transparency)
    coll_bytes: Dict[str, int]   # per-chip local result bytes from HLO text
    model_flops: float           # 6*N_active*D (train) or 2*N_active*tokens (serve)
    analytic_flops: float = 0.0  # trip-count-exact analytic model (global)
    analytic_bytes: float = 0.0
    analytic_coll: Optional[Dict[str, float]] = None  # per-chip, trip-exact
    spec: HardwareSpec = TPU_V5E

    @property
    def coll_total(self) -> int:
        return sum(self.coll_bytes.values())

    @property
    def coll_time_bytes(self) -> float:
        return sum(COLL_FACTOR[k] * v for k, v in self.coll_bytes.items())

    @property
    def t_compute(self) -> float:
        return self.spec.t_compute(self.analytic_flops / self.chips)

    @property
    def t_memory(self) -> float:
        return self.spec.t_memory(self.analytic_bytes / self.chips)

    @property
    def t_collective(self) -> float:
        """Per-chip collective seconds.

        Uses max(analytic, HLO-text) — the text counts while bodies once
        (lower bound); the analytic model is trip-count exact but
        first-order.
        """
        text = self.spec.t_collective(self.coll_time_bytes)
        ana = self.spec.t_collective((self.analytic_coll or {}).get("total", 0.0))
        return max(text, ana)

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.analytic_flops if self.analytic_flops else 0.0

    def to_dict(self) -> Dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops_raw": self.hlo_flops,
            "hlo_bytes_raw": self.hlo_bytes,
            "analytic_flops": self.analytic_flops,
            "analytic_bytes": self.analytic_bytes,
            "coll_bytes": self.coll_bytes,
            "coll_total": self.coll_total,
            "analytic_coll": self.analytic_coll or {},
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
        }


def analytic_cost(cfg, shape, *, remat: bool = True) -> Dict[str, float]:
    """Analytic FLOPs + HBM bytes for one step of (cfg, shape).

    Needed because XLA's HloCostAnalysis on the CPU backend counts a
    while-loop (lax.scan over layer units) body ONCE instead of
    trip-count times, so ``cost_analysis()`` under-reports scanned stacks
    by ~num_layers x. We therefore derive the roofline terms from this
    analytic model (exact for GEMMs, first-order for elementwise) and
    record the raw cost_analysis numbers alongside for transparency.

    Conventions:
        train:   fwd(1x) + bwd(2x) + remat recompute(1x) = 4x fwd FLOPs
        prefill: 1x fwd
        decode:  1x fwd over 1 token/seq; HBM bytes dominated by weight +
                 cache streaming.
    """
    from repro.config import BlockKind  # local import to avoid cycle

    B, S = shape.global_batch, shape.seq_len
    tokens = B * (S if shape.kind in ("train", "prefill") else 1)
    dt_bytes = 2 if cfg.dtype == "bfloat16" else 4

    # ---- matmul params touched per token (active) -> GEMM flops
    n_active = cfg.active_param_count()
    # embedding lookup is a gather, not a matmul; subtract one vocab table
    n_matmul = n_active - cfg.vocab_size * cfg.d_model
    gemm_flops = 2.0 * tokens * n_matmul

    # ---- attention score/value flops per layer kind
    attn_flops = 0.0
    hd, Hq = cfg.head_dim, cfg.num_heads
    for i, kind in enumerate(cfg.layer_pattern):
        if kind not in (BlockKind.ATTN_MLP, BlockKind.ATTN_MOE,
                        BlockKind.HYBRID_SHARED_ATTN):
            continue
        ak = cfg.attention_kind_at(i)
        if shape.kind in ("train", "prefill"):
            kv_eff = S if ak.value == "full" else min(cfg.sliding_window or S, S)
            # causal halves the average context; sliding window doesn't
            ctx = S / 2 if ak.value == "full" else kv_eff
            attn_flops += 4.0 * B * S * ctx * Hq * hd
        else:
            kv_eff = S if ak.value == "full" else min(cfg.sliding_window or S, S)
            attn_flops += 4.0 * B * kv_eff * Hq * hd

    # ---- SSM / RWKV recurrence flops
    ssm_flops = 0.0
    if cfg.ssm is not None:
        s = cfg.ssm
        d_inner = s.expand * cfg.d_model
        H = s.num_ssm_heads or d_inner // s.head_dim
        P, N, L = s.head_dim, s.state_dim, s.chunk_size
        for kind in cfg.layer_pattern:
            if kind == BlockKind.MAMBA2:
                if shape.kind in ("train", "prefill"):
                    # intra-chunk: scores 2*T*L*N + y 2*T*L*H*P (causal ~ /2)
                    ssm_flops += B * S * (L * N + L * H * P) \
                        + 4.0 * B * S * H * P * N  # states in/out
                else:
                    ssm_flops += 6.0 * B * H * P * N
            elif kind == BlockKind.RWKV6:
                per_tok = 6.0 * H * N * N  # state update + readout
                ssm_flops += (B * S if shape.kind in ("train", "prefill") else B) * per_tok

    fwd = gemm_flops + attn_flops + ssm_flops
    factor = (4.0 if remat else 3.0) if shape.kind == "train" else 1.0
    flops = fwd * factor

    # ---- HBM bytes
    param_bytes = cfg.param_count() * dt_bytes
    if shape.kind == "train":
        # params fwd+bwd+remat reads + grad writes + opt state rw (f32)
        pbytes = param_bytes * 4 + cfg.param_count() * 4 * 3
        act_bytes = 12.0 * tokens * cfg.d_model * dt_bytes * cfg.num_layers / 4
        logit_bytes = 4.0 * tokens * cfg.vocab_size
        hbm = pbytes + act_bytes + logit_bytes
    elif shape.kind == "prefill":
        hbm = param_bytes + 8.0 * tokens * cfg.d_model * dt_bytes * cfg.num_layers / 4 \
            + cache_bytes(cfg, shape)
    else:
        hbm = cfg.active_param_count() * dt_bytes + cache_bytes(cfg, shape)
    return {"flops": flops, "hbm_bytes": hbm, "fwd_flops": fwd}


def analytic_collectives(
    cfg, shape, *, policy: str = "fsdp", tp_acts: bool = True,
    data: int = 16, model: int = 16, pods: int = 1,
) -> Dict[str, float]:
    """Analytic per-chip collective bytes for one step.

    Needed for the same reason as ``analytic_cost``: the HLO text shows
    scan (while) bodies ONCE, so text-derived collective bytes are a lower
    bound that under-counts anything inside the layer scan by ~num_units x.
    First-order ring-collective model:

      weight all-gather (fsdp):  passes x param_bytes      (train: fwd+bwd+remat=3)
      grad sync (train):         2 x param_bytes           (ring all-reduce, bf16)
      TP activation all-reduce:  4 x toks_local x d_model x 4B x n_blocks
                                 (1 row-parallel AR fwd + ~2 bwd + 1 remat per block)
      ZeRO-1 pod sync:           2 x param_bytes across pods (multi-pod train)
    """
    dt_bytes = 2 if cfg.dtype == "bfloat16" else 4
    param_bytes = cfg.param_count() * dt_bytes
    # Routed-expert weights are ALWAYS expert-parallel (forced constraints)
    # and never gathered — only the dense remainder moves under FSDP.
    dense_bytes = (cfg.param_count() - cfg.expert_param_count()) * dt_bytes
    B, S = shape.global_batch, shape.seq_len
    toks_local = B * (S if shape.kind in ("train", "prefill") else 1) / data
    n_blocks = cfg.num_layers

    # grads are synced over the data axis PER SHARD: a chip holding 1/model
    # of the params moves 2 x its local shard bytes in the ring, not 2 x
    # the global total (replicate keeps full bytes).
    shard_div = 1 if policy == "replicate" else model

    out = {"weight_ag": 0.0, "grad_ar": 0.0, "tp_ar": 0.0, "pod_ar": 0.0}
    if shape.kind == "train":
        if policy == "fsdp":
            out["weight_ag"] = 3.0 * dense_bytes
        out["grad_ar"] = 2.0 * param_bytes / shard_div
        if pods > 1:
            out["pod_ar"] = 2.0 * param_bytes / shard_div
        if tp_acts and policy in ("fsdp", "tp"):
            out["tp_ar"] = 4.0 * toks_local * cfg.d_model * 4.0 * n_blocks
    else:
        if policy == "fsdp":
            out["weight_ag"] = 1.0 * dense_bytes / max(data, 1)  # amortized:
            # weights stay gathered across the (single) step; decode
            # re-gathers the data-sharded fraction only.
        if tp_acts and policy in ("fsdp", "tp"):
            out["tp_ar"] = 2.0 * toks_local * cfg.d_model * 4.0 * n_blocks
    out["total"] = sum(out.values())
    return out


def cache_bytes(cfg, shape) -> float:
    """Decode-state bytes read per step (KV caches + recurrent states)."""
    from repro.config import BlockKind

    B, S = shape.global_batch, shape.seq_len
    dt_bytes = 2 if cfg.dtype == "bfloat16" else 4
    total = 0.0
    for i, kind in enumerate(cfg.layer_pattern):
        if kind in (BlockKind.ATTN_MLP, BlockKind.ATTN_MOE,
                    BlockKind.HYBRID_SHARED_ATTN):
            ak = cfg.attention_kind_at(i)
            s_alloc = S if ak.value == "full" else min(cfg.sliding_window or S, S)
            total += 2.0 * B * cfg.num_kv_heads * s_alloc * cfg.head_dim * dt_bytes
        elif kind == BlockKind.MAMBA2 and cfg.ssm is not None:
            s = cfg.ssm
            d_inner = s.expand * cfg.d_model
            H = s.num_ssm_heads or d_inner // s.head_dim
            total += B * H * s.head_dim * s.state_dim * 4
        elif kind == BlockKind.RWKV6 and cfg.ssm is not None:
            H = cfg.d_model // cfg.ssm.head_dim
            total += B * H * cfg.ssm.head_dim ** 2 * 4
    return total


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS: 6*N_active*D for training; 2*N_active*tokens for serving."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch
