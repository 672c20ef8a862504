"""Sharding rules: map every parameter / engine array to mesh axes.

Megatron-style tensor parallelism expressed as GSPMD PartitionSpecs over the
stacked-layer param tree (models/llama.py):

  wq  [L, H, Hq, D]   -> heads on tp          (column-parallel)
  wk  [L, H, Hkv, D]  -> kv heads on tp
  wv  [L, H, Hkv, D]  -> kv heads on tp
  wo  [L, Hq, D, H]   -> heads on tp          (row-parallel; XLA inserts the
                                               all-reduce after the einsum)
  wg  [L, H, F]       -> F on tp              (column-parallel)
  wu  [L, H, F]       -> F on tp
  wd  [L, F, H]       -> F on tp              (row-parallel + all-reduce)
  embed [V, H]        -> replicated (lookup stays local)
  lm_head [H, V]      -> V on tp              (logits gathered at the end)
  norms               -> replicated
  KV pool [L, S, Hkv*D] -> kv heads on tp     (each chip caches its heads;
                                               heads are the outer factor of
                                               the merged minor axis)
  latent-attention models -> everything replicated: the cached row is shared
                             by all heads (no head split of the pool), and
                             the engine serves them one device a replica

The leading L axis carries "pp" when a pipeline axis is used (stage split =
contiguous layer ranges); kept None here — PP slicing happens above these
rules, not inside them.

GQA note: the clean head split needs the tensor degree to divide
num_kv_heads.  When it does not (e.g. 70B with 8 kv heads at degree 16),
the mesh factorizes the tensor axis into ("tp","tq") with tp | num_kv_heads
(parallel/mesh.py factor_tp_for_kv): q heads / MLP hidden / vocab shard
over BOTH axes (full degree), kv params and the KV pool shard over "tp"
alone — each kv head lives on tq chips (grouped head-sharing) instead of
every chip.  The decode attention einsums then shard with ZERO extra
collectives: q reshaped [B,S,Hkv,G,D] carries ("tp" on Hkv, "tq" on G), k
carries "tp" on Hkv, and the scores/output einsums contract only D, so
GSPMD keeps everything local until wo's row-parallel psum over
("tp","tq") — the same all-reduce the clean split already pays.  If the
degree shares no factor with num_kv_heads at all, tp=1 and the pool is
fully replicated (the old fallback, now the last resort).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig

Params = Dict[str, Any]


def _kv_axis(cfg: ModelConfig, mesh: Mesh) -> Optional[str]:
    """kv-head shard axis, or None (replicate) when tp doesn't divide."""
    tp = mesh.shape.get("tp", 1)
    if tp > 1 and cfg.num_kv_heads % tp == 0 and not cfg.is_latent:
        return "tp"
    return None


def _tensor_axes(mesh: Mesh):
    """The full-degree tensor axes: ("tp","tq") on grouped-GQA meshes,
    plain "tp" on meshes without a tq axis (legacy/test meshes)."""
    if mesh.shape.get("tq", 1) > 1:
        return ("tp", "tq")
    return "tp" if "tp" in mesh.axis_names else None


def param_specs(cfg: ModelConfig, mesh: Mesh) -> Params:
    """PartitionSpec pytree congruent with init_params' tree."""
    kv = _kv_axis(cfg, mesh)
    tx = _tensor_axes(mesh)
    layers: Params = {
        "ln_attn": P(),
        "ln_mlp": P(),
        "wq": P(None, None, tx, None),
        "wk": P(None, None, kv, None),
        "wv": P(None, None, kv, None),
        "wo": P(None, tx, None, None),
    }
    if cfg.is_moe:
        # MoE (models/ffn.py:_moe_block): experts over "ep", per-expert
        # FFN dim still Megatron-split over "tp" — ep x tp composes.  The
        # router stays replicated so every rank routes identically; GSPMD
        # inserts the expert-axis psum at the combine einsum.
        ep = "ep" if (
            mesh.shape.get("ep", 1) > 1
            and cfg.num_experts % mesh.shape["ep"] == 0
        ) else None
        layers["router"] = P()
        layers["wg"] = P(None, ep, None, tx)     # [L, E, H, F]
        layers["wu"] = P(None, ep, None, tx)
        layers["wd"] = P(None, ep, tx, None)     # [L, E, F, H]
    else:
        layers["wg"] = P(None, None, tx)
        layers["wu"] = P(None, None, tx)
        layers["wd"] = P(None, tx, None)
    specs: Params = {
        "embed": P(),
        "final_norm": P(),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, tx)
    return specs


def kv_pool_spec(cfg: ModelConfig, mesh: Mesh) -> P:
    """[L, SLOTS, Hkv*D] pool: cache each chip's kv heads locally.

    Heads are the outer factor of the merged minor axis, so sharding that
    axis tp-ways lands whole heads per chip (tp | Hkv per _kv_axis)."""
    return P(None, None, _kv_axis(cfg, mesh))


def shard_params(params: Params, cfg: ModelConfig, mesh: Mesh) -> Params:
    """Place a param pytree onto the mesh per the TP rules.

    Int8 QTensor leaves (models/quant.py) shard their `q` exactly like the
    dense weight; the per-output-channel scale follows the same spec with
    size-1 (contraction) dims unsharded.
    """
    from ..models.quant import QTensor

    if cfg.is_latent:
        # Served on one device a replica (the engine refuses a tp / ep mesh
        # over the latent pool): a 1-device mesh pins the replica, and every
        # leaf of the latent tree ("dense_layers" beside "layers") lands
        # there whole.
        return replicate(params, mesh)
    specs = param_specs(cfg, mesh)

    def place(x, spec):
        if isinstance(x, QTensor):
            axes = list(spec) + [None] * (x.q.ndim - len(spec))
            s_spec = P(*(
                ax if x.s.shape[i] != 1 else None
                for i, ax in enumerate(axes)
            ))
            return QTensor(
                q=jax.device_put(x.q, NamedSharding(mesh, spec)),
                s=jax.device_put(x.s, NamedSharding(mesh, s_spec)),
            )
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(
        place, params, specs, is_leaf=lambda x: isinstance(x, QTensor)
    )


def shard_kv_pool(k_pool, v_pool, cfg: ModelConfig, mesh: Mesh):
    from ..models.quant import QTensor

    sh = NamedSharding(mesh, kv_pool_spec(cfg, mesh))

    def place(pool):
        if isinstance(pool, QTensor):
            # int8 pool: rows follow the kv spec; the per-slot scale's
            # minor dim is 1 (unshardable) — replicate it
            s_sh = NamedSharding(mesh, P(None, None, None))
            return QTensor(q=jax.device_put(pool.q, sh),
                           s=jax.device_put(pool.s, s_sh))
        return jax.device_put(pool, sh)

    return place(k_pool), place(v_pool)


def replicate(tree, mesh: Mesh):
    sh = NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)
