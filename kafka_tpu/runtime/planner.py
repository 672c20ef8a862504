"""Memory-fit planner: pure arithmetic over model + engine + mesh shapes.

A serving framework must answer "does this config fit this topology, and at
what concurrency?" *before* anyone buys the topology.  The reference never
had to (its LLM compute was a remote gateway, src/llm/portkey.py); a local
TPU engine does.  This module computes per-device HBM bytes for a
(ModelConfig, engine shape, mesh) triple using THE SAME placement rules the
engine actually applies:

* weights follow parallel/sharding.py's PartitionSpecs — including the
  grouped-GQA factorization (parallel/mesh.py factor_tp_for_kv) that shards
  kv projections and the KV pool over the largest common divisor of the
  tensor degree and num_kv_heads, replicating each kv head only across its
  tq-group (70B at degree 16: 8-way kv shard, 2 chips per head — 8x less
  per-chip KV than the full replication this planner charged before);
* the KV pool is the [L, num_pages * page_size, Hkv*D] pair of
  runtime/kv_cache.py, k and v, layer axis split over pp
  (parallel/pipeline.py stages), head axis over gcd(tp, Hkv) — the
  grouped-GQA kv sub-axis (tq groups replicate);
* int8 weight quantization (models/quant.py) stores 1 byte/param + an f32
  scale per output channel; int8 KV halves pool bytes + per-page f32 scales.

Activation peaks are *estimates* (XLA's scratch is its own business), sized
from the dominant live tensors: the [S, V/tp] f32 prefill logits block, the
flash-prefill window gather, and the decode-time [B, V] f32 logits +
sampling workspace.  A fragmentation/scratch reserve (default 8%) absorbs
what the formulas do not model; `tests/test_planner.py` pins the known
ground truths (8B bf16 does NOT fit one v5e chip, 8B int8 DOES — both
observed on real hardware in round 4).

Known HBM budgets (public datasheet numbers):
  v5e  (v5 lite): 16 GiB/chip
  v5p:            95 GiB/chip
"""

from __future__ import annotations

import dataclasses
import os
import functools
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import AbstractMesh

from ..models.config import (
    CROSS,
    DELTA,
    ModelConfig,
    holds_rows,
)
from ..models.ffn import moe_dispatch_form
from ..models.init_params import init_params
from ..models.mixers.latent import PREFILL_WALK_KEYS
from ..models.quant import param_bytes, quantize_params
from ..parallel.sharding import param_specs
from .kv_cache import default_state_slots

GiB = 1024**3
MiB = 1024**2

# chip generation -> HBM bytes per chip (Google Cloud TPU documentation,
# per-chip "HBM capacity" of each generation's system architecture page)
HBM_BYTES = {
    "v5e": 16 * GiB,
    "v5p": 95 * GiB,
    "v6e": 32 * GiB,
    "v4": 32 * GiB,
}

# chip generation -> (peak dense bf16 FLOP/s, HBM bytes/s) per chip —
# the roofline the device-utilization estimator (ISSUE 10) divides the
# planner's modeled per-dispatch flop/byte costs by.  Same source as
# HBM_BYTES ("peak compute per chip (bf16)", "HBM bandwidth per chip").
CHIP_PEAKS = {
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v6e": (918e12, 1640e9),
    "v4": (275e12, 1228e9),
}

# EXACT jax `device_kind` string -> chip generation (the key of the two
# tables above).  Source: what `jax.devices()[0].device_kind` prints on
# each generation — note v5p reports plain "TPU v5", which a substring
# match on "v5" would file under v5e.  A TPU whose kind is not listed is
# an error (chip_for_device): a guessed roofline or HBM budget would be
# reported as a fact about a chip nobody looked up.
DEVICE_KINDS = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5": "v5p",
    "TPU v6 lite": "v6e",
}

PEAK_TFLOPS_ENV = "KAFKA_TPU_PEAK_TFLOPS"
PEAK_HBM_GBPS_ENV = "KAFKA_TPU_PEAK_HBM_GBPS"

# The device bytes the on-device grammar tables may take in all (the engine's
# _GrammarTables holds the live ones under it; llm/constrained.py refuses to
# compile a larger artifact), charged in every MemoryPlan unless on-device
# grammars are off.
GRAMMAR_ONDEVICE_ENV = "KAFKA_TPU_GRAMMAR_ONDEVICE"
GRAMMAR_TABLE_MB_ENV = "KAFKA_TPU_GRAMMAR_TABLE_MB"
_GRAMMAR_TABLE_MB_DEFAULT = 64


def grammar_ondevice_enabled() -> bool:
    return os.environ.get(GRAMMAR_ONDEVICE_ENV, "1") not in (
        "0", "false", "off"
    )


def grammar_table_cap_bytes() -> int:
    try:
        mb = float(os.environ.get(GRAMMAR_TABLE_MB_ENV, ""))
    except ValueError:
        mb = _GRAMMAR_TABLE_MB_DEFAULT
    if not mb:
        mb = _GRAMMAR_TABLE_MB_DEFAULT
    return int(mb * (1 << 20))


_DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2, "int8": 1}


def _bytes(dtype: str) -> int:
    return _DTYPE_BYTES[dtype]


def _kv_shard(cfg: ModelConfig, tp: int, kv_shard: Optional[int] = None) -> int:
    """kv-head shard factor — delegates to parallel/mesh.py
    factor_tp_for_kv so the plan charges exactly what the engine places:
    the tensor degree factorizes into tp_kv * tq with tp_kv =
    gcd(degree, Hkv); kv params and the pool shard tp_kv-ways and
    replicate only across the tq groups (grouped GQA head-sharing).  A
    degree sharing no factor with Hkv degrades to full replication.

    `kv_shard` overrides the grouped default for configs where the mesh
    keeps the plain tensor axis (ulysses CP, pp stages) —
    plan_for_serving resolves it via the SAME resolve_tensor_axes call
    the server uses, so plan and placement cannot drift."""
    if cfg.is_latent:
        return 1  # one row a token shared by all heads: nothing to split
    if kv_shard is not None:
        return kv_shard
    from ..parallel.mesh import factor_tp_for_kv

    return factor_tp_for_kv(tp, cfg.num_kv_heads)[0]


def chip_for_device(dev) -> Optional[str]:
    """Chip generation of a live jax device by its exact `device_kind`
    (DEVICE_KINDS).  None off-TPU (CPU tests have no datasheet); a TPU
    whose kind is not in the table raises."""
    if getattr(dev, "platform", None) != "tpu":
        return None
    kind = getattr(dev, "device_kind", "")
    try:
        return DEVICE_KINDS[kind]
    except KeyError:
        raise ValueError(
            f"unknown TPU device_kind {kind!r}: add its row to "
            f"runtime/planner.py DEVICE_KINDS (known: "
            f"{sorted(DEVICE_KINDS)})"
        ) from None


def hbm_for_device(dev) -> Optional[int]:
    """HBM budget for a live jax device: the runtime's bytes_limit when
    reported, else the datasheet number for its chip generation.  None
    off-TPU; raises on a TPU kind DEVICE_KINDS does not list."""
    chip = chip_for_device(dev)
    stats = getattr(dev, "memory_stats", lambda: None)() or {}
    if stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    return HBM_BYTES[chip] if chip is not None else None


@dataclasses.dataclass(frozen=True)
class MemoryPlan:
    """Per-device byte budget for one serving configuration."""

    model: str
    mesh: Dict[str, int]              # {"tp":..,"sp":..,"pp":..,"ep":..}
    hbm_bytes: int                    # budget per chip
    reserve_frac: float               # scratch/fragmentation allowance
    weight_bytes: int                 # per device
    kv_pool_bytes: int                # per device (both k and v)
    activation_bytes: int             # estimated peak live activations
    kv_replicated: bool               # kv not sharded the full tensor
                                      # degree (gcd(tp, Hkv) < tp): pool
                                      # replicated across tq groups
    kv_bytes_per_token: int           # per device, k+v, all layers
    window_tokens: int                # configured attention window
    # Machine-readable grouped tp×tq factorization (the layout the bytes
    # above are charged under): kv params + pool shard kv_shard-ways and
    # replicate across tq groups.  mesh["tp"] stays the REQUESTED tensor
    # degree (= kv_shard * tq when grouped); consumers should read these
    # fields, not parse the free-text notes.
    kv_shard: int = 1
    tq: int = 1
    # On-device constrained-decoding grammar tables (ISSUE 7): the
    # KAFKA_TPU_GRAMMAR_TABLE_MB reservation, replicated per device.  The
    # engine's _GrammarTables.register enforces the same figure as a
    # COMBINED budget over all live grammars' padded tables (over-budget
    # registrations degrade to the host mask path), so this charge is the
    # true worst case.  0 when on-device grammar is disabled.
    grammar_table_bytes: int = 0
    # Tiered KV cache host-pool budget (ISSUE 9): HOST RAM per engine
    # replica (KAFKA_TPU_KV_HOST_TIER_MB), charged here so a deployment
    # plan states the full memory footprint — but deliberately NOT part
    # of total_bytes, which is the per-chip HBM budget.  0 = tier off.
    kv_host_tier_bytes: int = 0
    # State slots of a model with a recurrent state beside its pages
    # (runtime/kv_cache.make_state_arrays), as the device lays them out.
    state_bytes: int = 0
    notes: str = ""

    @property
    def total_bytes(self) -> int:
        return (self.weight_bytes + self.kv_pool_bytes + self.state_bytes
                + self.activation_bytes + self.grammar_table_bytes)

    @property
    def usable_bytes(self) -> int:
        return int(self.hbm_bytes * (1.0 - self.reserve_frac))

    @property
    def fits(self) -> bool:
        return self.total_bytes <= self.usable_bytes

    @property
    def headroom_bytes(self) -> int:
        return self.usable_bytes - self.total_bytes

    @property
    def max_concurrent_windows(self) -> int:
        """How many FULL attention windows of KV the leftover HBM holds —
        the honest "max concurrent N-token threads" number (weights and
        activations charged first; the configured pool is not)."""
        free = self.usable_bytes - self.weight_bytes - self.activation_bytes
        per_window = self.kv_bytes_per_token * self.window_tokens
        return max(0, free // per_window) if per_window else 0

    def summary(self) -> Dict[str, object]:
        return {
            "model": self.model,
            "mesh": self.mesh,
            "hbm_gib": round(self.hbm_bytes / GiB, 2),
            "weight_gib": round(self.weight_bytes / GiB, 3),
            "kv_pool_gib": round(self.kv_pool_bytes / GiB, 3),
            "state_mib": round(self.state_bytes / MiB, 2),
            "activation_gib": round(self.activation_bytes / GiB, 3),
            "total_gib": round(self.total_bytes / GiB, 3),
            "usable_gib": round(self.usable_bytes / GiB, 3),
            "fits": self.fits,
            "headroom_gib": round(self.headroom_bytes / GiB, 3),
            "kv_replicated": self.kv_replicated,
            "kv_shard": self.kv_shard,
            "tq": self.tq,
            "grammar_table_mib": round(self.grammar_table_bytes / MiB, 2),
            "kv_host_tier_mib": round(self.kv_host_tier_bytes / MiB, 2),
            "window_tokens": self.window_tokens,
            "max_concurrent_windows": self.max_concurrent_windows,
            "notes": self.notes,
        }


@functools.lru_cache(maxsize=64)
def _abstract_params(cfg: ModelConfig, quantize: str):
    """The parameter tree `init_params` would build (through
    `quantize_params` for "int8") as shapes and dtypes, no array made: 0.01-
    0.34 s a configuration of the benchmark's on a CPU, once a process."""
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    if quantize == "int8":
        tree = jax.eval_shape(lambda t: quantize_params(t, cfg), tree)
    return tree


def weight_bytes_per_device(
    cfg: ModelConfig,
    *,
    tp: int = 1,
    pp: int = 1,
    ep: int = 1,
    quantize: str = "",
    kv_shard: Optional[int] = None,
) -> int:
    """Per-device weight bytes: `param_bytes` of the abstract tree (a new
    leaf is counted the day the initialiser has it), each leaf divided by the
    mesh axes parallel/sharding.param_specs names for it and the stacked axis
    by `pp`.  A tree of another shape than the homogeneous stack's is served
    one device a replica (the engine refuses it a mesh): counted whole."""
    tree = _abstract_params(cfg, quantize)
    if tp * pp * ep == 1 or cfg.lead_tree or cfg.hybrid_decoder:
        return param_bytes(tree)
    kv_shard = _kv_shard(cfg, tp, kv_shard)
    # the mesh the server would build, as axis sizes: tp = kv_shard x tq
    mesh = AbstractMesh((kv_shard, tp // kv_shard, ep), ("tp", "tq", "ep"))
    specs = param_specs(cfg, mesh)
    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = [k.key for k in path if hasattr(k, "key")]
        spec = specs
        for key in keys:
            # (a leaf the rules do not name is replicated)
            spec = spec.get(key, ()) if isinstance(spec, dict) else spec
        shards = pp if keys[0] == "layers" else 1
        for size, axes in zip(leaf.shape, spec):
            if axes is not None and size > 1:
                # (an int8 leaf's scale keeps its contracted axes at 1)
                for axis in (axes,) if isinstance(axes, str) else axes:
                    shards *= mesh.shape[axis]
        total += leaf.size * leaf.dtype.itemsize // shards
    return total


def _hybrid_second_half_bytes(cfg: ModelConfig, wb: int) -> int:
    """The hybrid decoder's cross periods (gated memory unit, cross
    attention, an MLP each): the half a prefill launch runs on each lane's
    last real row alone (models/hybrid.forward)."""
    h, f, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    hq = cfg.num_heads
    n_x = cfg.layers_of(CROSS)
    diff = 4 * d * 4 + 2 * d * wb  # lambda vectors, sub-layer norm
    common = 2 * n_x * (3 * h * f + 4 * h) * wb
    cross = n_x * ((2 * h * hq * d + hq * d + h) * wb + diff)
    gmu = n_x * 2 * h * cfg.mamba_d_inner * wb
    return common + cross + gmu


def state_bytes_per_device(cfg: ModelConfig, state_slots: int) -> int:
    """The state slots as the device holds them, whatever kind of layer
    they are the state of (`cfg.state_shapes`): float32, the second-minor
    axis of each leaf padded to the 8-row sublane tile (a conv tail of 3 or
    of 2 rows takes 8)."""
    return 4 * cfg.state_layers * state_slots * sum(
        -(-rows // 8) * 8 * cols for _, (rows, cols) in cfg.state_shapes())


def kv_pool_bytes_per_device(
    cfg: ModelConfig,
    *,
    num_pages: int,
    page_size: int,
    tp: int = 1,
    pp: int = 1,
    kv_dtype: str = "bfloat16",
    kv_shard: Optional[int] = None,
) -> int:
    """Both pool arrays (k + v), [L/pp, num_pages*page_size, row] with the
    rows `cfg.kv_row_widths` gives."""
    kv_shard = _kv_shard(cfg, tp, kv_shard)
    slots = num_pages * page_size
    b = (cfg.kv_values_per_token // pp * slots // kv_shard
         * _bytes(kv_dtype))
    if kv_dtype == "int8":
        # per-slot f32 scales, k and v (int8 KV quantization tier)
        b += cfg.num_layers // pp * slots * 2 * 4
    return b


def kv_bytes_per_token(
    cfg: ModelConfig, *, tp: int = 1, pp: int = 1,
    kv_dtype: str = "bfloat16", kv_shard: Optional[int] = None,
) -> int:
    kv_shard = _kv_shard(cfg, tp, kv_shard)
    return cfg.kv_values_per_token // pp // kv_shard * _bytes(kv_dtype)


def activation_bytes_estimate(
    cfg: ModelConfig,
    *,
    max_batch: int,
    prefill_bucket: int,
    window: int,
    tp: int = 1,
    sp: int = 1,
) -> int:
    """Peak live activations, from the dominant tensors.

    Prefill (chunk S over sp ranks, heads/F over tp):
      logits block  S/sp * V/tp * 4   (f32, the [S, V] einsum output)
      hidden trio   S/sp * (H + 2*F/tp) * 2
      window gather S * Hkv*D * 2 * 2 (XLA fallback reads k+v windows;
                    the flash kernel streams pages instead, but plan for
                    the portable path)
    Decode: B * V * 4 * 3 (logits + top-k sort workspace ~2 copies).
    """
    V, H, F = cfg.vocab_size, cfg.hidden_size, cfg.intermediate_size
    # k + v values of one token, one layer (the widest kind's)
    kv_row = max(sum(cfg.kv_row_widths(kind)) for kind in cfg.kinds)
    s_local = max(1, prefill_bucket // max(sp, 1))
    prefill = (
        # (a hybrid decoder's prefill computes its lanes' last rows alone)
        (1 if cfg.has_state else s_local) * (V // tp) * 4
        + s_local * (H + 2 * F // tp) * 2
        + window * kv_row * 2
    )
    if cfg.hybrid_decoder:
        # the scan's float32 operands: x, dt, y, z and the projections
        # around them, [S, inner] each, and B / C broadcast along 128 lanes
        prefill += s_local * (cfg.mamba_d_inner * 4 * 6
                              + cfg.mamba_d_state * 128 * 4 * 2)
    elif cfg.ssd_heads:
        # (an SSD mixer, beside attention or alone in its layer) the input projection and the convolution over [x | B | C] in
        # float32, then the chunk kernel's operands (dt x, B, C, the
        # log-decay's cumulative sum) and its output, and the gated norm
        d_ssm = cfg.ssd_heads * cfg.ssd_head_dim
        prefill += s_local * ((2 * d_ssm + cfg.ssd_conv_dim) * 2
                              + cfg.ssd_conv_dim * 4 * 3 + d_ssm * 4 * 4)
    elif DELTA in cfg.layer_types:
        # [q | k | v] and the convolution over them, then the chunk kernel's
        # six float32 operands (q, k, beta k, beta v, the log-decay and its
        # cumulative sum) and its output, [S, heads x head size] each
        # (a third of the convolutions' channels: heads x head size where
        # the heads are square)
        prefill += s_local * (cfg.delta_conv_dim // 3) * (
            3 * 2 + 3 * 4 * 2 + 7 * 4)
    elif cfg.has_state:
        # a short convolution's [B | C | u] and its float32 products
        prefill += s_local * H * (3 * 2 + 2 * 4)
    if cfg.hc_mult > 1:
        # a widened residual stream: the carry's n rows a token beside the
        # one the trio counts, as much again for a mix's result, and the
        # mappings' float32 normed copy of all n
        prefill += s_local * H * ((2 * cfg.hc_mult - 1) * 2
                                  + cfg.hc_mult * 4)
    decode = max_batch * V * 4 * 3 + max_batch * window * kv_row * 2
    return max(prefill, decode)


def plan_memory(
    cfg: ModelConfig,
    *,
    tp: int = 1,
    sp: int = 1,
    pp: int = 1,
    ep: int = 1,
    num_pages: int,
    page_size: int,
    max_pages_per_seq: int,
    max_batch: int,
    prefill_bucket: int = 512,
    quantize: str = "",
    kv_dtype: str = "bfloat16",
    hbm_bytes: Optional[int] = None,
    chip: str = "v5e",
    reserve_frac: float = 0.08,
    kv_shard: Optional[int] = None,
    grammar_table_bytes: Optional[int] = None,
    kv_host_tier_bytes: int = 0,
    state_slots: int = 0,
) -> MemoryPlan:
    if hbm_bytes is None:
        hbm_bytes = HBM_BYTES[chip]
    if grammar_table_bytes is None:
        # charge the on-device constrained-decoding table reservation
        # (the compiler caps artifacts at this size; tables replicate
        # per device) unless the feature is disabled
        grammar_table_bytes = (
            grammar_table_cap_bytes() if grammar_ondevice_enabled() else 0
        )
    kv_shard = _kv_shard(cfg, tp, kv_shard)
    kv_replicated = tp > 1 and kv_shard < tp
    window = max_pages_per_seq * page_size
    plan = MemoryPlan(
        model=cfg.name,
        mesh={"tp": tp, "sp": sp, "pp": pp, "ep": ep},
        hbm_bytes=hbm_bytes,
        reserve_frac=reserve_frac,
        weight_bytes=weight_bytes_per_device(
            cfg, tp=tp, pp=pp, ep=ep, quantize=quantize, kv_shard=kv_shard
        ),
        kv_pool_bytes=kv_pool_bytes_per_device(
            cfg, num_pages=num_pages, page_size=page_size, tp=tp, pp=pp,
            kv_dtype=kv_dtype, kv_shard=kv_shard,
        ),
        activation_bytes=activation_bytes_estimate(
            cfg, max_batch=max_batch, prefill_bucket=prefill_bucket,
            window=window, tp=tp, sp=sp,
        ),
        kv_replicated=kv_replicated,
        kv_bytes_per_token=kv_bytes_per_token(
            cfg, tp=tp, pp=pp, kv_dtype=kv_dtype, kv_shard=kv_shard
        ),
        window_tokens=window,
        # unconditional: tp = kv_shard * tq always holds, so kv_shard=1
        # with tp=8 reports tq=8 (full 8-way replication), not tq=1
        kv_shard=kv_shard,
        tq=tp // kv_shard,
        grammar_table_bytes=grammar_table_bytes,
        kv_host_tier_bytes=kv_host_tier_bytes,
        state_bytes=state_bytes_per_device(cfg, state_slots),
        notes=(
            (
                f"grouped GQA layout: tensor degree {tp} factorizes "
                f"tp={kv_shard} x tq={tp // kv_shard}; kv params+pool "
                f"shard {kv_shard}-ways, each kv head replicated on "
                f"{tp // kv_shard} chips (parallel/mesh.py "
                "factor_tp_for_kv)"
                if kv_shard > 1 else
                "kv params+pool fully replicated per chip: the mesh "
                f"keeps the plain tensor axis (degree {tp}) and it does "
                f"not divide num_kv_heads ({cfg.num_kv_heads})"
            )
            if kv_replicated else ""
        ),
    )
    return plan


def device_peaks(dev) -> tuple:
    """(peak FLOP/s, peak HBM bytes/s, source) roofline for a live jax
    device — the denominator of the MFU / HBM-bandwidth-utilization
    estimator (ISSUE 10).

    KAFKA_TPU_PEAK_TFLOPS / KAFKA_TPU_PEAK_HBM_GBPS override everything
    (CPU tests, derated shared machines); else the datasheet row for the
    device's exact `device_kind`.  Off-TPU there is no datasheet:
    (None, None, "unknown"), and the estimator reports achieved FLOP/s
    and GB/s without ratios.  A TPU kind DEVICE_KINDS does not list
    raises rather than inventing a roofline.
    """
    import os as _os

    env_tf = _os.environ.get(PEAK_TFLOPS_ENV)
    env_bw = _os.environ.get(PEAK_HBM_GBPS_ENV)
    if env_tf or env_bw:
        try:
            return (
                float(env_tf) * 1e12 if env_tf else None,
                float(env_bw) * 1e9 if env_bw else None,
                "env",
            )
        except ValueError:
            pass
    chip = chip_for_device(dev)
    if chip is None:
        return None, None, "unknown"
    return (*CHIP_PEAKS[chip], "datasheet")


@dataclasses.dataclass(frozen=True)
class DispatchCostModel:
    """Per-device flop/byte cost of one engine dispatch, from the same
    shape arithmetic the memory plan uses (ISSUE 10).

    The engine calls the cost methods at every dispatch site with its
    host-known shapes (new tokens sampled, total KV context attended);
    the products divide by measured inter-dispatch wall time in
    runtime/metrics.py to yield MFU and HBM-bandwidth utilization.
    Deliberately an ESTIMATE: matmul flops use the 2·params convention
    (embedding lookups and norms are noise), attention uses 4·H·D per
    (query, kv) pair, and per-device sharing divides evenly across the
    mesh — replication factors (tq groups, norms) undercount a few
    percent, which is far inside the wall-time attribution error.
    """

    flops_per_token: float       # per device: matmul flops for 1 token
    attn_flops_per_kv: float     # per device: per (query, kv-token) pair
    weight_bytes: int            # per device: read once per dispatch step
    kv_bytes_per_token: int      # per device: one token's k+v row
    # What ONE prefill launch pays, by what pays it (prefill_launch_cost):
    # the products every dispatched row takes part in, those a lane's last
    # real row alone does, one (token, expert) pick through every routed
    # layer, the attention pairs by kind of layer, and what a latent launch's
    # walk expands whatever its rows hold.
    row_flops: float = 0.0
    lane_flops: float = 0.0
    pick_flops: float = 0.0
    attn_kinds: Tuple[Tuple[float, Optional[int]], ...] = ()  # (a pair, window)
    # The rows of a launch that attend: 0 = those that hold a token (flash
    # prefill skips the q blocks of the others), else every bucket row,
    # rounded up to this many (1: XLA prefill and the latent walk's XLA
    # fold; 128: latent_prefill_fold's whole lane tiles).
    attend_row_tile: int = 0
    # (a context key through W_kvb in every layer of the kind, window), and
    # the keys a trip of the walk reads: latent models alone
    walk_kinds: Tuple[Tuple[float, Optional[int]], ...] = ()
    walk_chunk_keys: int = 1
    # (experts held, experts the router knows, picks a token, sharded or
    # int8: models/ffn.moe_dispatch_form's arguments); None = no router
    moe: Optional[Tuple[int, int, int, bool, bool]] = None
    expert_bytes: int = 0        # every routed expert held, all layers
    launch_bytes: int = 0        # weights a launch reads whatever it holds

    def decode_cost(self, new_tokens: int, kv_tokens: int,
                    steps: int = 1) -> tuple:
        """One decode dispatch advancing `new_tokens` lanes by `steps`
        fused steps, attending ~`kv_tokens` total context per step.
        Decode is HBM-bound: every weight byte streams once per step and
        the batch's whole KV window is gathered per step."""
        flops = steps * kv_tokens * self.attn_flops_per_kv \
            + new_tokens * self.flops_per_token
        bytes_ = steps * (self.weight_bytes
                          + kv_tokens * self.kv_bytes_per_token) \
            + new_tokens * self.kv_bytes_per_token
        return flops, bytes_

    def prefill_cost(self, chunk_tokens: int, start_tokens: int) -> tuple:
        """One prefill chunk of `chunk_tokens` starting at position
        `start_tokens`: causal attention pairs = chunk·start + chunk²/2;
        KV reads cover the materialized window once, writes the chunk."""
        pairs = chunk_tokens * start_tokens + chunk_tokens * chunk_tokens / 2
        flops = (chunk_tokens * self.flops_per_token
                 + pairs * self.attn_flops_per_kv)
        bytes_ = (self.weight_bytes
                  + (start_tokens + chunk_tokens) * self.kv_bytes_per_token
                  + chunk_tokens * self.kv_bytes_per_token)
        return flops, bytes_

    def prefill_launch_cost(self, rows: int, tokens: int,
                            start: int) -> tuple:
        """(flops, bytes) of ONE prefill launch of `rows` bucket rows of
        which the first `tokens` hold a token, from position `start`: what
        the chunk plan prices (prefill_launches).  Rows that hold no token
        still take part in every dense product (projections, dense and
        shared-expert MLPs, a state model's row-wise half).  Whether they
        attend is the path's (`attend_row_tile`): in XLA prefill every
        bucket row attends the context, masked or not; the flash-prefill
        kernel (GQA on Pallas) skips the q blocks that hold no token; the
        latent walk folds every bucket row against every chunk it walks, and
        its kernel pads the bucket to whole lane tiles of 128 first, so a
        64-row launch folds 128.  A latent launch also pays its WALK, which
        no row count changes (`walk_kinds`): every trip expands the chunk's
        latent rows through W_kvb for all heads, over the whole context in a
        full layer and from the chunk that holds the window's first key in a
        sliding one.  Rows that hold no token enter no expert group under
        token dispatch, which the routed block takes by
        models/ffn.moe_dispatch_form's rule: below it every held expert
        multiplies every row.  A hybrid decoder's second half and the head
        run on each lane's last real row.  The weights are read once: every
        routed expert under dense dispatch, under token dispatch those some
        token picked."""
        flops = rows * self.row_flops + self.lane_flops
        tile = self.attend_row_tile
        q = rows + -rows % tile if tile else tokens
        for pair_flops, window in self.attn_kinds:
            keys = start + q / 2
            flops += q * (keys if window is None else min(keys, window)) \
                * pair_flops
        flops += self.walk_flops(tokens, start)
        bytes_ = (self.launch_bytes
                  + (start + 2 * tokens) * self.kv_bytes_per_token)
        if self.moe is not None:
            held, routed, top_k, sharded, int8 = self.moe
            if moe_dispatch_form(rows, held, top_k, sharded, routed,
                                 int8) == "dense":
                flops += rows * held * self.pick_flops
                bytes_ += self.expert_bytes
            else:
                flops += tokens * top_k * (held / routed) * self.pick_flops
                bytes_ += self.expert_bytes * (
                    1.0 - (1.0 - top_k / routed) ** tokens)
        return flops, bytes_

    def walk_flops(self, tokens: int, start: int) -> float:
        """The flops of a latent prefill launch's walk (0 for a model that
        is not latent): every context key a layer's trips read, from key 0
        in a full layer and from the chunk that holds the first query's
        window in a sliding one (at most the window and one chunk more),
        through W_kvb.  The rows of the bucket do not enter."""
        flops = 0.0
        for key_flops, window in self.walk_kinds:
            first = 0 if window is None else (
                max(start - window + 1, 0)
                // self.walk_chunk_keys * self.walk_chunk_keys)
            flops += (start + tokens - first) * key_flops
        return flops

    def launch_price(self, peak_flops: float, peak_hbm_bps: float
                     ) -> Callable[[int, int, int], float]:
        """price(rows, tokens, start): the modeled seconds of one prefill
        launch on a chip of these peaks: the slower of its two bounds, and
        the walk in series.  A launch has two costs that no row count
        changes, the weights it reads and the context it walks; one is
        bound by HBM in the MLP's ops and one by the MXU in attention's, so
        a small launch pays both, not the larger."""
        def price(rows: int, tokens: int, start: int) -> float:
            flops, bytes_ = self.prefill_launch_cost(rows, tokens, start)
            walk = self.walk_flops(tokens, start)
            return (max((flops - walk) / peak_flops, bytes_ / peak_hbm_bps)
                    + walk / peak_flops)
        return price

    def verify_cost(self, query_tokens: int, kv_tokens: int,
                    attn_pairs: Optional[float] = None) -> tuple:
        """One speculative verify dispatch scoring `query_tokens` total
        candidate positions (sum over lanes of cand+1) against
        `kv_tokens` of context.  `attn_pairs` is the (query, kv-token)
        pair count — each of a lane's K+1 queries attends that lane's
        whole context, so pairs ~= kv_tokens x per-lane query width, NOT
        kv_tokens (the decode convention); callers pass it, the
        query_tokens fallback covers width-1 degenerate calls.  Bytes
        stay kv_tokens-based: the kernel streams each KV page once per
        lane regardless of query width."""
        if attn_pairs is None:
            attn_pairs = float(kv_tokens)
        flops = (query_tokens * self.flops_per_token
                 + attn_pairs * self.attn_flops_per_kv)
        bytes_ = (self.weight_bytes + kv_tokens * self.kv_bytes_per_token
                  + query_tokens * self.kv_bytes_per_token)
        return flops, bytes_


def dispatch_cost_model(
    cfg: ModelConfig,
    *,
    n_devices: int = 1,
    weight_bytes_total: Optional[int] = None,
    kv_dtype_bytes: int = 2,
    kv_replication: int = 1,
    int8_experts: bool = False,
) -> DispatchCostModel:
    """Build the per-device dispatch cost model for an engine.

    `weight_bytes_total` is the engine's ACTUAL materialized parameter
    bytes when known (models/quant.param_bytes — exact for int8 trees);
    falls back to the planner's bf16 arithmetic.  `kv_replication` is the
    tq factor (grouped GQA replicates each kv head across its tq group,
    so per-device KV traffic does not shrink by the full device count).
    `int8_experts`: the routed experts' leaves are quantized
    (models/ffn.experts_int8), which the routed block's form asks.
    """
    if weight_bytes_total is None:
        weight_bytes_total = weight_bytes_per_device(cfg, tp=1)
    wb = _bytes(cfg.dtype)
    # params from the unsharded bf16 arithmetic (stable vs quantization)
    params_total = weight_bytes_per_device(cfg, tp=1) / wb
    n = max(1, n_devices)
    kv_row = cfg.kv_values_per_token * kv_dtype_bytes
    # per (query, key) pair and head: 2 flops a value of the score and of
    # the weighted sum.  GQA: D + D.  Latent, absorbed: the score runs over
    # the latent and the rotary lanes, the sum over the latent ones.
    pair = (2 * cfg.kv_lora_rank + cfg.qk_rope_head_dim if cfg.is_latent
            else 2 * cfg.head_dim)
    # The launch price's split of the parameters (prefill_launch_cost): the
    # routed experts, the table a launch gathers rows of (and, tied, its
    # head), a hybrid decoder's second half; every other leaf multiplies
    # every dispatched row.
    table = cfg.vocab_size * cfg.hidden_size
    # (an ungated expert is two matrices, a gated one three)
    expert = ((2 if cfg.mlp_act == "relu2" else 3) * cfg.hidden_size
              * cfg.intermediate_size if cfg.is_moe else 0)
    routed_layers = cfg.routed_layers
    experts_total = routed_layers * cfg.num_experts * expert
    second_half = (_hybrid_second_half_bytes(cfg, wb) / wb
                   if cfg.hybrid_decoder else 0.0)
    gathered = 0 if cfg.tie_word_embeddings else table
    # (a model with a state takes the head of each lane's last real row;
    # the others' prefill programs multiply every row by it and pick one)
    last_row = (table if cfg.has_state else 0) + second_half
    row_params = params_total - experts_total - gathered - last_row
    # bytes by the share of the ACTUAL tree (an int8 tree's leaves shrink
    # together, near enough)
    per_param = weight_bytes_total / params_total / n

    def pair_flops(kind: str) -> float:
        """One (query, key) pair through every layer of `kind`."""
        if cfg.is_latent:
            g = cfg.geometry_of(kind)
            heads, width = g.num_heads, 2 * g.kv_lora_rank + g.qk_rope_head_dim
        else:
            heads, width = cfg.num_heads, 2 * cfg.head_dim
        return 2.0 * cfg.layers_of(kind) * heads * width / n

    def key_flops(kind: str) -> float:
        """One context key through W_kvb (every head's k_nope and v from the
        latent row) in every layer of `kind`: what a trip of
        models/mixers/latent._latent_prefill_walk expands, whoever attends
        it."""
        g = cfg.geometry_of(kind)
        return (2.0 * cfg.layers_of(kind) * g.kv_lora_rank * g.num_heads
                * (g.qk_nope_head_dim + g.v_head_dim) / n)

    # The rows of a launch that attend, by the path the layer body takes
    # (DispatchCostModel.attend_row_tile).  The latent walk folds the whole
    # bucket, in its kernel padded to lane tiles; flash prefill, which skips
    # the q blocks past the last token, runs for GQA on Pallas on one device
    # over a pool that is not int8 (one byte a value: that one prefills
    # through the dequantizing XLA gather, as a mesh does); every other
    # prefill is XLA's over all bucket rows.
    kernels = cfg.attention_backend == "pallas"
    if cfg.is_latent:
        attend_row_tile = 128 if kernels else 1
    else:
        attend_row_tile = 0 if kernels and n == 1 and kv_dtype_bytes > 1 else 1
    return DispatchCostModel(
        flops_per_token=2.0 * params_total / n,
        # (a model with a state: the layers that attend, over rows of their
        # own or, cross attention, another layer's)
        attn_flops_per_kv=2.0 * (cfg.num_layers if not cfg.has_state else
                                 sum(holds_rows(kind) or kind == CROSS
                                     for kind in cfg.layer_types))
        * cfg.num_heads * pair / n,
        weight_bytes=int(weight_bytes_total // n),
        kv_bytes_per_token=int(kv_row * max(1, kv_replication) // n),
        row_flops=2.0 * row_params / n,
        lane_flops=2.0 * last_row / n,
        pick_flops=2.0 * routed_layers * expert / n,
        attn_kinds=tuple((pair_flops(kind), cfg.window_of(kind))
                         for kind in cfg.kinds
                         if cfg.is_latent or holds_rows(kind)),
        attend_row_tile=attend_row_tile,
        walk_kinds=tuple((key_flops(kind), cfg.window_of(kind))
                         for kind in cfg.kinds) if cfg.is_latent else (),
        walk_chunk_keys=PREFILL_WALK_KEYS,
        moe=((cfg.num_experts, cfg.num_router_experts,
              cfg.num_experts_per_tok, n > 1, int8_experts)
             if cfg.is_moe else None),
        expert_bytes=int(experts_total * per_param),
        launch_bytes=int((params_total - experts_total - gathered)
                         * per_param),
    )


# A split plan must be modeled this much cheaper than the launches the
# first-bucket-that-holds-it rule makes: the price sees device seconds alone,
# and a launch is also a host dispatch and one more place in the device's
# queue between the request and its first token.
PREFILL_SPLIT_MIN_SAVING = 0.10

Launches = Tuple[Tuple[int, int], ...]  # (bucket rows, tokens held) a launch


def first_fit_bucket(remaining: int, buckets: Sequence[int]) -> int:
    """The first bucket of the ladder that holds `remaining`, else the
    largest."""
    return next((b for b in buckets if b >= remaining), buckets[-1])


def first_fit_launches(remaining: int, buckets: Sequence[int]) -> Launches:
    """The launches of the rule without a price: the first bucket that
    holds what is left, until nothing is."""
    out = []
    while remaining > 0:
        b = first_fit_bucket(remaining, buckets)
        out.append((b, min(b, remaining)))
        remaining -= out[-1][1]
    return tuple(out)


def prefill_launches(
    remaining: int,
    buckets: Sequence[int],
    price: Optional[Callable[[int, int, int], float]] = None,
    start: int = 0,
    page_size: int = 1,
) -> Launches:
    """The chunk plan: the launches that cover `remaining` prompt tokens
    from position `start` at the least `price(rows, tokens, start)`, the
    modeled seconds of one launch.  The candidates are the rule without a
    price (the first bucket that holds the remainder; past the largest,
    whole launches of it) and, for each smaller bucket the remainder fills
    at least once, as many FULL launches of it as fit, each followed by the
    plan of the rest: every launch but the last is full, and ends on a page
    boundary (a resumed prefill must start on one), so a bucket that is no
    multiple of `page_size` splits nothing.  The cheapest candidate wins if
    it is PREFILL_SPLIT_MIN_SAVING cheaper than the first; ties go to fewer
    launches.  No price: the rule without one."""
    if remaining <= 0:
        return ()
    if price is None:
        return first_fit_launches(remaining, buckets)

    def cost(launches: Launches) -> float:
        total, at = 0.0, start
        for rows, tokens in launches:
            total += price(rows, tokens, at)
            at += tokens
        return total

    def full_then_rest(b: int) -> Launches:
        n = max(1, remaining // b)
        head = ((b, min(b, remaining)),) * n
        done = sum(t for _, t in head)
        return head + prefill_launches(remaining - done, buckets, price,
                                       start + done, page_size)

    b0 = first_fit_bucket(remaining, buckets)
    first = full_then_rest(b0)
    splits = [full_then_rest(b) for b in buckets
              if b < b0 and b % page_size == 0]
    if not splits:
        return first
    least, _, best = min((cost(ls), len(ls), ls) for ls in splits)
    if least <= (1.0 - PREFILL_SPLIT_MIN_SAVING) * cost(first):
        return best
    return first


def plan_for_serving(scfg, hbm_bytes: Optional[int] = None,
                     chip: str = "v5e",
                     model_cfg: Optional[ModelConfig] = None) -> MemoryPlan:
    """Plan from a ServingConfig (server/config.py).

    `model_cfg` overrides the registry lookup — the server passes the model
    it actually loaded (checkpoint / tiny configs differ from model_name).
    """
    if model_cfg is None:
        from ..models.config import get_config

        model_cfg = get_config(scfg.model_name)
    # resolve (tp, tq) the way the server will build the mesh — ulysses/pp
    # configs keep the plain axis and fall back to full kv replication,
    # and the plan must charge for THAT, not the grouped layout
    from ..parallel.mesh import resolve_tensor_axes

    tpk, tq = resolve_tensor_axes(
        scfg.tp_size, model_cfg.num_kv_heads,
        cp_strategy=getattr(scfg, "cp_strategy", "ring"),
        sp=scfg.sp_size, pp=scfg.pp_size,
    )
    kv_shard = tpk if (tq > 1 or model_cfg.num_kv_heads % tpk == 0) else 1
    return plan_memory(
        model_cfg,
        tp=scfg.tp_size, sp=scfg.sp_size, pp=scfg.pp_size, ep=scfg.ep_size,
        num_pages=scfg.num_pages, page_size=scfg.page_size,
        max_pages_per_seq=scfg.max_pages_per_seq, max_batch=scfg.max_batch,
        prefill_bucket=max(scfg.prefill_buckets),
        quantize=scfg.quantize,
        kv_dtype=getattr(scfg, "kv_quantize", "") or "bfloat16",
        hbm_bytes=hbm_bytes, chip=chip, kv_shard=kv_shard,
        # host-RAM tier budget (not HBM): stated in the plan so capacity
        # reviews see the full footprint of a tiered deployment
        kv_host_tier_bytes=getattr(scfg, "kv_host_tier_mb", 0) * MiB,
        state_slots=(default_state_slots(scfg.max_batch)
                     if model_cfg.has_state else 0),
    )


# ---------------------------------------------------------------------------
# Live HBM accounting (ISSUE 18, leg b): reconcile the boot-time plan
# against what the device actually holds, at step cadence.

HBM_WATERMARK_ENV = "KAFKA_TPU_HBM_WATERMARK"
HBM_POLL_ENV = "KAFKA_TPU_HBM_POLL_S"


def _watermark_frac() -> Optional[float]:
    """Headroom watermark as a fraction of the device byte limit.
    Explicitly set -> that fraction (clamped to [0, 1)).  Unset ->
    0.03 for device-sourced samples and DISABLED for plan-synthesized
    ones: a barely-fitting plan on CPU smoke would otherwise hold an
    hbm_pressure anomaly forever on numbers that are a prediction, not
    a measurement."""
    raw = __import__("os").environ.get(HBM_WATERMARK_ENV)
    if raw is None or raw == "":
        return None
    try:
        return min(0.99, max(0.0, float(raw)))
    except ValueError:
        return None


class MemoryMonitor:
    """Per-engine live HBM gauge set (engine-thread single-writer).

    ``poll()`` reads every device's ``memory_stats()`` (throttled to
    ``KAFKA_TPU_HBM_POLL_S``, default 1s — one host RPC per device,
    never on the dispatch hot path more than that) and publishes one
    immutable section dict; readers (``/metrics``, ``/admin/signals``,
    the flight recorder's ``hbm_pressure`` detector) grab the latest
    reference torn-free.

    Devices without ``memory_stats`` (CPU smoke) synthesize the sample
    from the :class:`MemoryPlan` itself (``source: "plan"``,
    ``plan_skew`` pinned at 1.0) so every consumer downstream — the
    gauges, the signals section, the ladder input — exercises the same
    code path the TPU runs.
    """

    def __init__(self, devices, plan: Optional[MemoryPlan] = None,
                 poll_s: Optional[float] = None):
        import os as _os
        self.devices = list(devices)
        self.plan = plan
        if poll_s is None:
            try:
                poll_s = float(_os.environ.get(HBM_POLL_ENV, "1.0"))
            except ValueError:
                poll_s = 1.0
        self.poll_s = max(0.0, poll_s)
        explicit = _watermark_frac()
        self.watermark_frac = explicit
        self._watermark_explicit = explicit is not None
        self._last_poll_t: Optional[float] = None
        self._last: Optional[Dict[str, object]] = None
        self.polls = 0

    # -- sampling --------------------------------------------------------

    def poll(self, now: Optional[float] = None,
             force: bool = False) -> Optional[Dict[str, object]]:
        """Refresh the sample when the throttle allows; returns the
        current section either way (None before the first poll)."""
        import time as _time
        now = _time.monotonic() if now is None else now
        if (not force and self._last_poll_t is not None
                and now - self._last_poll_t < self.poll_s):
            return self._last
        self._last_poll_t = now
        self._last = self._sample()
        self.polls += 1
        return self._last

    def _sample(self) -> Dict[str, object]:
        per_dev = []
        for d in self.devices:
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats or not stats.get("bytes_limit"):
                continue
            per_dev.append({
                "device": str(getattr(d, "id", len(per_dev))),
                "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "bytes_peak": int(stats.get(
                    "peak_bytes_in_use", stats.get("bytes_in_use", 0))),
                "bytes_limit": int(stats["bytes_limit"]),
            })
        plan = self.plan
        if per_dev:
            # worst device bounds the fleet: the plan is per-device
            in_use = max(d["bytes_in_use"] for d in per_dev)
            peak = max(d["bytes_peak"] for d in per_dev)
            limit = min(d["bytes_limit"] for d in per_dev)
            source = "device"
        elif plan is not None:
            in_use = plan.total_bytes
            peak = plan.total_bytes
            limit = plan.usable_bytes
            source = "plan"
        else:
            return {
                "source": "none", "hbm_bytes_in_use": 0,
                "hbm_bytes_peak": 0, "hbm_bytes_limit": 0,
                "hbm_headroom_bytes": 0, "hbm_plan_skew": 0.0,
                "hbm_pressure": 0, "hbm_component_bytes": {},
                "devices": [],
            }
        headroom = limit - in_use
        skew = (in_use / plan.total_bytes
                if plan is not None and plan.total_bytes else 0.0)
        wm = self.watermark_frac
        if wm is None:
            wm = 0.03 if source == "device" else None
        pressure = (wm is not None and limit > 0
                    and headroom < wm * limit)
        return {
            "source": source,
            "hbm_bytes_in_use": int(in_use),
            "hbm_bytes_peak": int(peak),
            "hbm_bytes_limit": int(limit),
            "hbm_headroom_bytes": int(headroom),
            "hbm_plan_skew": round(skew, 4),
            "hbm_pressure": 1 if pressure else 0,
            "hbm_component_bytes": self._attribution(in_use),
            "devices": per_dev,
        }

    def _attribution(self, in_use: int) -> Dict[str, int]:
        """Measured bytes reconciled against the plan's line items:
        each planned component at its planned charge, with the
        residual (gather staging, XLA scratch, fragmentation — real
        allocations the plan folds into reserve_frac) surfaced as
        ``unattributed``.  A strongly negative residual means the plan
        OVER-charges (plan_skew < 1): components larger than life."""
        plan = self.plan
        if plan is None:
            return {}
        comp = {
            "weights": plan.weight_bytes,
            "kv_pool": plan.kv_pool_bytes,
            "activations": plan.activation_bytes,
            "grammar_tables": plan.grammar_table_bytes,
        }
        comp["unattributed"] = int(in_use) - plan.total_bytes
        return comp

    # -- export ----------------------------------------------------------

    def section(self) -> Optional[Dict[str, object]]:
        """Latest sample (the ``memory`` metrics/signals section; keys
        registered as MEMORY_METRIC_KEYS in metrics.py)."""
        return self._last

    def pressure(self) -> bool:
        s = self._last
        return bool(s and s.get("hbm_pressure"))

    def headroom_frac(self) -> Optional[float]:
        """Headroom as a fraction of the limit (autoscaler sizing input:
        size against MEASURED headroom, not planned)."""
        s = self._last
        if not s or not s.get("hbm_bytes_limit"):
            return None
        return s["hbm_headroom_bytes"] / s["hbm_bytes_limit"]
