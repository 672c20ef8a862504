"""Decode's step of a depthwise causal convolution whose tail is a layer's
STATE (`models/mixers/state._tail_conv_silu`: the delta layout's three
convolutions side by side, the SSD mixers' one over [x | B | C]), on the slot
AS IT IS STORED.

A lane's tail, taps - 1 rows of C channels in float32, lies in its slot over
8 rows of W = (taps - 1) C / 8 values (`models/config._tail_layout`: a leaf
whose second-minor axis is 3 is tiled to 8 on the device).  Tap row j of
channel c is at flat offset j C + c of the slot: row (j C + c) // W, column
(j C + c) % W.  Cut the channels into pieces of u = gcd(C, W): inside a piece
every tap row is ONE run of u columns of ONE stored row, and every boundary is
static.  So one pass over the block does the whole step and no [B, taps - 1,
C] array exists anywhere:

    out[c]      = SiLU(sum_j w[j, c] * seq[j, c] (+ bias[c]))
    tail'[j, c] = seq[j + 1, c],        seq = the tail's rows ++ the new row

(a lane with no real row writes back what it read), the taps summed in the
order `_tail_conv_silu` sums them.  In decode lane i is slot i, so a grid step
takes `lanes_a_step` NEIGHBOURING slots as one block of the aliased leaf and a
piece is a [lanes, u] array that fills its sublanes (a lane alone leaves seven
of a register's eight rows empty).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES_A_STEP = 8   # slots a grid step holds (a register's sublanes)
VMEM_LIMIT = 64 << 20
_F32 = jnp.float32


def piece(C: int, slot) -> int:
    """Channels of one piece of a tail of C channels a row stored as `slot`
    (rows, W): the widest run that no tap row's stored rows cut."""
    return math.gcd(C, slot[1])


def tiles(taps: int, C: int, slot) -> bool:
    """Whether the step kernel takes this tail: laid over 8 rows
    (`_tail_layout`), and a piece whole 128-lane tiles, so that C, W and C % W
    are (Solar-Open2's (8, 9216) of 24,576 channels: pieces of 24 tiles;
    Nemotron-H's (8, 2304) of 6,144: 6; Falcon-H1's (8, 1920) of 5,120: 5;
    not Granite's (8, 3168) of 8,448: 8.25)."""
    rows, W = slot
    return (taps > 1 and rows == 8 and rows * W == (taps - 1) * C
            and piece(C, slot) % 128 == 0)


def lanes_a_step(B: int) -> int:
    """Lanes of a grid step: LANES_A_STEP where they divide the call's."""
    n = LANES_A_STEP
    while B % n:
        n //= 2
    return n


def _step_kernel(layer_ref, real_ref, x_ref, w_ref, *refs, C, taps):
    """`lanes` neighbouring slots' blocks in, the shifted blocks out (the same
    bytes of the aliased leaf), piece by piece.  refs: the bias where there
    is one, then the slots' blocks in, the rows out, the blocks out."""
    del layer_ref
    b_ref = refs[0] if len(refs) == 4 else None
    s_ref, o_ref, s_out_ref = refs[-3:]
    W = s_ref.shape[3]
    u = piece(C, s_ref.shape[2:])
    real = real_ref[0] > 0                               # [lanes, 1]
    for p in range(C // u):
        cs = slice(p * u, (p + 1) * u)
        at = [divmod(j * C + p * u, W) for j in range(taps - 1)]
        seq = [s_ref[0, :, r, c:c + u] for r, c in at] + [x_ref[0, :, cs]]
        acc = w_ref[0:1, cs] * seq[0]
        for j in range(1, taps):
            acc = acc + w_ref[j:j + 1, cs] * seq[j]
        if b_ref is not None:
            acc = acc + b_ref[:, cs]
        o_ref[0, :, cs] = jax.nn.silu(acc)
        for j, (r, c) in enumerate(at):
            s_out_ref[0, :, r, c:c + u] = jnp.where(real, seq[j + 1], seq[j])


@functools.partial(jax.jit, static_argnames=("interpret",))
def tail_conv_step(leaf, layer, lens, x, w, bias=None, *,
                   interpret: bool = False):
    """Decode's one row a lane, lane i in slot i.  leaf [L, n_slots, 8, W]
    f32; layer [] int32; lens [B] int32 (0: the lane is not decoding, its slot
    is written back as read); x [B, C] f32, the new row; w [taps, C] f32 (tap
    taps - 1 multiplies the new row); bias [C] f32 or None -> (out [B, C] f32,
    leaf).  `leaf` is aliased to the result: donate it."""
    B, C = x.shape
    taps = w.shape[0]
    n = lanes_a_step(B)
    rows, W = leaf.shape[2:]

    def lanes(width):
        return pl.BlockSpec((1, n, width), lambda b, *_: (b, 0, 0))

    def whole(a):
        return pl.BlockSpec(a.shape, lambda b, *_: (0, 0))

    state = pl.BlockSpec((1, n, rows, W), lambda b, layer: (layer[0], b, 0, 0))
    kernel = functools.partial(_step_kernel, C=C, taps=taps)
    operands = [x.reshape(B // n, n, C), w]
    in_specs = [lanes(1), lanes(C), whole(w)]
    if bias is not None:
        operands.append(bias.reshape(1, C))
        in_specs.append(whole(operands[-1]))
    out, leaf = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // n,),
            in_specs=in_specs + [state],
            out_specs=[lanes(C), state],
        ),
        # the leaf is HELD in HBM, in and out (the aliased operand takes the
        # result's memory space): a leaf that fits beside the kernel's VMEM
        # (Falcon-H1's 55 MB) XLA otherwise prefetches whole into fast memory
        # ahead of every call and copies back after it, 135 us a layer
        out_shape=[jax.ShapeDtypeStruct((B // n, n, C), _F32),
                   pltpu.HBM(leaf.shape, leaf.dtype)],
        # operands count the scalar-prefetch argument: the leaf is the last
        input_output_aliases={len(operands) + 2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="tail_conv_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      (lens > 0).astype(jnp.int32).reshape(B // n, n, 1), *operands, leaf)
    return out.reshape(B, C), leaf
