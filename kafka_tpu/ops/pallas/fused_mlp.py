"""Fused decode-MLP block: rmsnorm + SwiGLU + residual in one kernel.

MEASURED OUTCOME (round 5, a since-deleted micro-benchmark on an earlier
v5e machine; not measured on the current one): this kernel does NOT beat
XLA's own formulation at decode shapes and is therefore NOT wired into
the serving path.  At llama-3.2-1b shapes (H=2048, F=8192, L=16, B=8),
16-layer MLP stack per pass:

    XLA 3-einsum scan    2.235 ms   (720 GB/s of weight stream)
    this kernel          2.721 ms   (592 GB/s)
    XLA int8 scan        1.058 ms   (762 GB/s effective)
    this kernel int8     1.768 ms   (456 GB/s)

i.e. XLA already streams the MLP trio at ~88-93% of the chip's nominal
819 GB/s — there is no inter-op bubble for a handable fusion to reclaim,
and Mosaic's small-batch (B=8 sublane) matmul pipeline is measurably
weaker than XLA's.  The kernel is kept in-tree, tested for numerics
(tests/test_fused_mlp.py), as the recorded ablation VERDICT r4 #1 called
for if the fusion lever turned out to be a dead end on this platform —
plus the per-output-channel post-scaling trick it demonstrates (see
below) which int8 serving inherits.

The original rationale (COVERAGE roofline): the b8 decode step spends
~4.1 ms in the layer sweep against a 2.4 ms weight-streaming floor.  The
MLP trio (wg/wu/wd) is ~85% of a Llama layer's weight bytes; as three
separate XLA matmuls with elementwise ops between them, each op would pay
its own pipeline ramp — except measurement shows XLA's scheduler already
overlaps them to roofline.  Design of the kernel, kept for reference:

  out = h + wd^T( silu(nx @ wg_t) * (nx @ wu_t) ),   nx = rmsnorm(h) * ln

* grid = (F // block_f,): one program per F-tile.  Step 0 computes the
  f32 rmsnorm into VMEM scratch (persistent across the sequential TPU
  grid); every step contracts its [H, bf] wg/wu tiles and [bf, H] wd tile,
  accumulating the down-projection in f32 scratch; the last step adds the
  residual and writes out.
* block_f adapts to VMEM: largest divisor of F (multiple of 128) keeping
  the double-buffered tile set under ~10 MB of the ~16 MB budget.
* int8 (models/quant.py QTensors): tiles arrive int8 — HALF the HBM
  stream — and dequantize on the VPU per tile with the same
  (q * s_f32) -> bf16 element rounding as the XLA path's fused dequant.
* batch stays as the block's sublane dim ([B, H] blocks, B = max_batch):
  decode batches are 8-64 rows, far under the MXU's 128 — these matmuls
  are bandwidth-bound, which is exactly why the DMA pipeline is the lever.

Numerics: matches the XLA path op-for-op (f32 norm, bf16 matmul operands
with f32 accumulation cast once per projection, bf16 silu/residual) but
not bit-for-bit (accumulation order differs tile-wise); engines under
either backend are token-compared in tests/test_fused_mlp.py, the same
contract the paged-attention kernel ships under.

No reference analog: the reference ran no local model (its compute lived
behind src/llm/portkey.py); SURVEY §2.3 sanctions Pallas kernels for the
serving hot loop.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# double-buffered (wg + wu + wd) tile budget; VMEM is ~16 MB/core and the
# persistent scratch (nx/acc/h blocks) + output need the rest
_TILE_BUDGET_BYTES = 10 * 1024 * 1024


def pick_block_f(H: int, F: int, weight_bytes: int) -> Optional[int]:
    """Largest 128-multiple divisor of F whose double-buffered tile set
    (2 buffers x 3 weights x [H or F-tile] x block_f) fits the budget."""
    best = None
    bf = 128
    while bf <= F:
        if F % bf == 0 and 2 * 3 * H * bf * weight_bytes <= _TILE_BUDGET_BYTES:
            best = bf
        bf *= 2
    return best


def _kernel(
    h_ref,      # [B, H] activation dtype — residual input
    ln_ref,     # [1, H] norm weight
    wg_ref,     # [H, bf] (bf16 or int8)
    wu_ref,     # [H, bf]
    wd_ref,     # [bf, H]
    sg_ref,     # [1, bf] f32 or None
    su_ref,     # [1, bf] f32 or None
    sd_ref,     # [1, H] f32 or None
    out_ref,    # [B, H]
    nx_ref,     # scratch [B, H] activation dtype — normed input
    acc_ref,    # scratch [B, H] f32 — down-projection accumulator
    *,
    eps: float,
    quantized: bool,
):
    i = pl.program_id(0)
    dt = h_ref.dtype

    @pl.when(i == 0)
    def _prologue():
        x32 = h_ref[...].astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        normed = x32 * jax.lax.rsqrt(var + eps)
        nx_ref[...] = (normed * ln_ref[...].astype(jnp.float32)).astype(dt)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def mm(x, w_ref):
        # int8 operands upcast to the activation dtype at the MXU's door
        # (exact for |q| <= 127); per-output-channel scales are applied to
        # the small OUTPUT, never the [H, tile] operand — they commute out
        # of the contraction (the same algebra the int8 logits head uses,
        # models/llama.py), and operand-side dequant is VPU-bound at a
        # million elements per tile (measured 1.66x slower end-to-end)
        return jax.lax.dot_general(
            x, w_ref[...].astype(dt),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    nx = nx_ref[...]
    g = mm(nx, wg_ref)
    u = mm(nx, wu_ref)
    if quantized:
        g = g * sg_ref[...]  # [B, bf] * [1, bf] f32
        u = u * su_ref[...]
    g = g.astype(dt)
    u = u.astype(dt)
    # silu with the sigmoid in f32: Mosaic mis-lowers logistic on bf16
    # vectors (vector.broadcast f32->bf16 verification failure); one extra
    # f32->bf16 rounding vs the XLA path's bf16 silu, inside tolerance
    g32 = g.astype(jnp.float32)
    p = (g32 * jax.nn.sigmoid(g32)).astype(dt) * u
    acc_ref[...] += mm(p, wd_ref)

    @pl.when(i == pl.num_programs(0) - 1)
    def _epilogue():
        # residual add in the activation dtype — the XLA path's h + mlp(x).
        # wd's per-output-H scale is constant across F-tiles: applied once
        # to the finished f32 accumulator.
        acc = acc_ref[...]
        if quantized:
            acc = acc * sd_ref[...]
        out_ref[...] = h_ref[...] + acc.astype(dt)


@functools.partial(
    jax.jit, static_argnames=("eps", "block_f", "interpret")
)
def fused_mlp_block(
    h: jnp.ndarray,            # [B, H] activations (residual stream)
    ln_w: jnp.ndarray,         # [H] rmsnorm weight
    wg: jnp.ndarray,           # [H, F] bf16/int8
    wu: jnp.ndarray,           # [H, F]
    wd: jnp.ndarray,           # [F, H]
    sg: Optional[jnp.ndarray] = None,   # [1, F] f32 scales (int8 only)
    su: Optional[jnp.ndarray] = None,   # [1, F]
    sd: Optional[jnp.ndarray] = None,   # [1, H]
    *,
    eps: float,
    block_f: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """h + SwiGLU_mlp(rmsnorm(h) * ln_w).  Returns [B, H] in h.dtype."""
    B, H = h.shape
    F = wg.shape[1]
    quantized = sg is not None
    if block_f is None:
        block_f = pick_block_f(H, F, wg.dtype.itemsize)
    if block_f is None or F % block_f:
        raise ValueError(
            f"no F-tile fits: H={H} F={F} itemsize={wg.dtype.itemsize}"
        )
    grid = (F // block_f,)

    full = lambda i: (0, 0)  # noqa: E731 — constant-index (resident) block
    specs = [
        pl.BlockSpec((B, H), full),                      # h
        pl.BlockSpec((1, H), full),                      # ln
        pl.BlockSpec((H, block_f), lambda i: (0, i)),    # wg tile
        pl.BlockSpec((H, block_f), lambda i: (0, i)),    # wu tile
        pl.BlockSpec((block_f, H), lambda i: (i, 0)),    # wd tile
    ]
    args = [h, ln_w.reshape(1, H)]
    args += [wg, wu, wd]
    if quantized:
        specs += [
            pl.BlockSpec((1, block_f), lambda i: (0, i)),  # sg tile
            pl.BlockSpec((1, block_f), lambda i: (0, i)),  # su tile
            pl.BlockSpec((1, H), full),                    # sd
        ]
        args += [sg, su, sd]
    else:
        # pallas has no optional refs: thread zero-size placeholders
        specs += [
            pl.BlockSpec((1, 1), full),
            pl.BlockSpec((1, 1), full),
            pl.BlockSpec((1, 1), full),
        ]
        z = jnp.zeros((1, 1), jnp.float32)
        args += [z, z, z]

    return pl.pallas_call(
        functools.partial(_kernel, eps=eps, quantized=quantized),
        grid=grid,
        in_specs=specs,
        out_specs=pl.BlockSpec((B, H), full),
        out_shape=jax.ShapeDtypeStruct((B, H), h.dtype),
        scratch_shapes=[
            pltpu.VMEM((B, H), h.dtype),       # nx
            pltpu.VMEM((B, H), jnp.float32),   # acc
        ],
        interpret=interpret,
    )(*args)
