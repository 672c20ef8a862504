"""The widened residual stream (`cfg.hc_mult` = n > 1: `xing4_0`, on latent
attention).  The hidden state in the layer scan's carry is n rows a token,
[B, T, n * C], widened once under `embed` and collapsed once under `head`;
every sublayer reads ONE row mixed from them and writes all n back, by
per-token mappings (`_hc_in` / `_hc_out`, the only code that knows; leaves
`hc_<site>_*` beside the norms).  With n = 1 they are `h` and `h + y` and not
an op is traced (tests/test_lowered_pins.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..ops.norms import rms_norm
from .config import ModelConfig
from .quant import Params


# The two sublayers of a layer, each with mappings of its own where the
# residual stream is widened (`cfg.hc_mult` > 1): leaves `hc_<site>_phi`
# [nC, n + n + n^2] (ONE matrix: H~_pre | H~_post | H~_res, split after the
# one product), `hc_<site>_bias` [n + n + n^2], `hc_<site>_alpha` [3] (both
# float32) and `hc_<site>_norm` [nC].
HC_SITES = ("attn", "mlp")


@partial(jax.jit, static_argnames="cfg")
def _sinkhorn(logits: jnp.ndarray, cfg: ModelConfig):
    """[..., n * n] float32 logits (a row's matrix row-major) -> the doubly
    stochastic matrix as n x n arrays [...], res[i][j]: exp of the clamped
    logits, then `cfg.hc_sinkhorn_iters` rounds of (each row by its sum +
    eps; each column by its sum + eps).  All the rounds, unrolled: no early
    exit.  Entry by entry on purpose: a sum over an axis of 4 is a reduction
    XLA fuses nothing across (eighty fusions a site on the v5e's compiler);
    sums of four arrays are elementwise, and the rounds compile to ONE.
    Jitted so that its 1,300 equations are traced once a shape and lowered
    as one function the sites call (XLA inlines it): unjitted, six sites a
    program cost a boot 25 s of tracing."""
    n = cfg.hc_mult
    m = jnp.exp(jnp.clip(logits, cfg.hc_res_clamp_min, cfg.hc_res_clamp_max))
    m = [[m[..., i * n + j] for j in range(n)] for i in range(n)]
    for _ in range(cfg.hc_sinkhorn_iters):
        for i in range(n):
            inv = 1.0 / (sum(m[i]) + cfg.hc_eps)
            m[i] = [v * inv for v in m[i]]
        for j in range(n):
            inv = 1.0 / (sum(m[i][j] for i in range(n)) + cfg.hc_eps)
            for i in range(n):
                m[i][j] = m[i][j] * inv
    return tuple(tuple(row) for row in m)


def _hc_rows(h: jnp.ndarray, n: int):
    """The stream's n rows of a token, float32: h is [B, T, n * C], row j at
    lanes j * C .. (j + 1) * C (a [.., n, C] array would be tiled with its
    second-minor axis padded from 4 to 8 or 16 sublanes on the device)."""
    c = h.shape[-1] // n
    return [h[..., j * c:(j + 1) * c].astype(jnp.float32) for j in range(n)]


def _hc_in(h: jnp.ndarray, lp: Params, site: str, cfg: ModelConfig):
    """Ahead of a sublayer: (u, maps).  One row a token (`cfg.hc_mult` 1): h
    itself and None, and not an op traced.  n rows: the site's per-token
    mappings from the normed stream (`hc_map`: float32, the one product with
    `hc_<site>_phi` at full precision), u = H_pre X (`hc_mix`) and maps =
    (H_post [B, T, n], H_res as `_sinkhorn` gives it) for `_hc_out`."""
    n = cfg.hc_mult
    if n == 1:
        return h, None
    with jax.named_scope("hc_map"):
        x = h.astype(jnp.float32)
        x = x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        x = x * lp[f"hc_{site}_norm"].astype(jnp.float32)
        t = jnp.einsum("btk,km->btm", x,
                       lp[f"hc_{site}_phi"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        alpha, bias = lp[f"hc_{site}_alpha"], lp[f"hc_{site}_bias"]
        pre = jax.nn.sigmoid(alpha[0] * t[..., :n] + bias[:n])
        post = 2.0 * jax.nn.sigmoid(
            alpha[1] * t[..., n:2 * n] + bias[n:2 * n])
        res = _sinkhorn(alpha[2] * t[..., 2 * n:] + bias[2 * n:], cfg)
    with jax.named_scope("hc_mix"):
        rows = _hc_rows(h, n)
        u = sum(pre[..., j, None] * rows[j] for j in range(n)).astype(h.dtype)
    return u, (post, res)


def _hc_out(h: jnp.ndarray, y: jnp.ndarray, maps, scope: str,
            mult: float = 1.0, norm=None) -> jnp.ndarray:
    """After a sublayer: one row a token, `h + y` under `scope` (where the
    add always sat), `h + mult * y` where the model publishes a
    `residual_multiplier` (`mult` != 1: the sublayer's output scaled in the
    stream's dtype, inside the add's scope, folded into no weight); n rows,
    X <- H_res X + H_post^T y under `hc_mix`.  `norm` (weight, eps): the
    reordered norm (`cfg.norm_position` "post"), h + RMSNorm(y), inside the
    add's scope too; None, and not an op traced, elsewhere."""
    if maps is None:
        with jax.named_scope(scope):
            if norm is not None:
                y = rms_norm(y, *norm)
            if mult != 1.0:
                y = y * jnp.asarray(mult, y.dtype)
            return h + y
    post, res = maps
    n = post.shape[-1]
    with jax.named_scope("hc_mix"):
        rows, y32 = _hc_rows(h, n), y.astype(jnp.float32)
        return jnp.concatenate(
            [sum(res[i][j][..., None] * rows[j] for j in range(n))
             + post[..., i, None] * y32 for i in range(n)],
            axis=-1).astype(h.dtype)
