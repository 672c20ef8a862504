"""Int8 weight-only quantization for the serving matmuls.

Decode throughput on one chip is HBM-bound: at batch 8 the 1B model's
weight stream is ~80% of per-step traffic, and Llama-3-8B in bf16 (16 GB)
does not fit a v5e chip at all.  Weight-only int8 halves (vs bf16) the
bytes every decode step reads and makes 8B-on-one-chip serveable — the
BASELINE headline metric's literal configuration.

Scheme: symmetric per-output-channel int8.  Each quantized leaf becomes a
`QTensor(q=int8, s=bf16 scale)` where the scale broadcasts over the
contraction axis, so `q.astype(bf16) * s` reconstructs the weight.  The
dequantize runs INSIDE the jitted step at each use site
(`_w` below): XLA fuses the convert+multiply into the matmul's
operand read, so HBM traffic stays int8-sized and the MXU still sees bf16
operands — the standard weight-only serving pattern on TPU.  Activations,
norms, the MoE router, and the KV cache are untouched.

Quality: per-channel symmetric int8 keeps |w - deq(w)| <= s/2 per element
(~0.4% of the channel's max); the bench records the greedy token match
rate vs the bf16 model as the shipped sanity check.

No reference analog (the reference ran no local model at all); SURVEY §2.3
names quantized matmul as sanctioned native-tier work.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from .config import ModelConfig

Params = Dict[str, Any]


class QTensor(NamedTuple):
    """Symmetric per-channel int8 weight: `q.astype(dt) * s` dequantizes.

    A NamedTuple so it is a pytree node: jax.tree operations, jit closure
    capture, donation, and device_put all treat q/s as ordinary leaves.
    """

    q: jnp.ndarray  # int8, original weight shape
    s: jnp.ndarray  # f32 scale, broadcastable (contraction dims = 1)

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):  # reported dtype = storage dtype (bench traffic math)
        return self.q.dtype


def quantize_array(w: jnp.ndarray, contract_axes) -> QTensor:
    """Per-output-channel symmetric int8 over the given contraction axes."""
    contract_axes = tuple(contract_axes)
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=contract_axes,
                   keepdims=True)
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / s), -127, 127).astype(
        jnp.int8
    )
    # scales stay f32: per-channel they are ~1/contraction_dim of the
    # weight bytes, and bf16-rounding them would add avoidable error to
    # every reconstructed element
    return QTensor(q=q, s=s)


def dequantize(w: Any, dtype) -> jnp.ndarray:
    """QTensor -> dense (fused into the consuming matmul under jit).

    The multiply keeps the scale in f32 (int8->dtype is exact for |q|<=127;
    dtype*f32 promotes to f32) and rounds ONCE at the end — casting the
    scale to bf16 first would re-add the rounding error f32 scale storage
    exists to avoid.
    """
    if isinstance(w, QTensor):
        return (w.q.astype(dtype) * w.s).astype(dtype)
    return w


def _w(lp: Params, name: str, dtype) -> jnp.ndarray:
    """Fetch a weight, dequantizing int8 QTensors in-graph (XLA fuses the
    convert into the matmul's operand read, keeping HBM traffic
    int8-sized)."""
    return dequantize(lp[name], dtype)


# Contraction axes per layer-stacked weight (models/init_params.py layouts).
# Axis 0 is the layer stack; scales are per (layer, output-channel).
_CONTRACT = {
    "wq": (1,),        # [L, H, hq, d]   contract H
    "wk": (1,),
    "wv": (1,),
    "wo": (1, 2),      # [L, hq, d, H]   contract hq, d
    "wg": (1,),        # [L, H, F]       contract H
    "wu": (1,),
    "wd": (1,),        # [L, F, H]       contract F
}
_CONTRACT_MOE = {
    "wg": (2,),        # [L, E, H, F]    contract H
    "wu": (2,),
    "wd": (2,),        # [L, E, F, H]    contract F
}


def quantize_params(params: Params, cfg: ModelConfig) -> Params:
    """Quantize the serving matmul weights of a Llama/Mixtral pytree.

    embed is quantized per-row ([V, H], contract H): the row gather
    dequantizes per looked-up token, and for tied embeddings the logits
    matmul streams the same int8 table.  Norms and the MoE router stay
    dense (tiny, accuracy-critical).
    """
    if cfg.is_latent:
        raise NotImplementedError(
            "int8 weight quantization of a latent-attention model is not "
            "built: its tree (wkva, wkvb, shared experts, dense_layers) has "
            "no contraction table here")
    if cfg.lone_layers or cfg.mlp_act != "silu":
        raise NotImplementedError(
            "int8 weight quantization of a one-sublayer pattern is not "
            "built: its ungated experts (wu, wd alone) and its mixers are "
            "stacked per kind (`ffn`, `attn`) and have no contraction table "
            "here")
    if cfg.mixer_then_ffn:
        raise NotImplementedError(
            "int8 weight quantization of the mixer-then-feed-forward layout "
            "(`granitemoehybrid`) is not built: its mixers are stacked per "
            "kind (`attn`), and neither they nor the shared expert have a "
            "contraction table here")
    if cfg.delta_gate == "head" and cfg.delta_heads:
        raise NotImplementedError(
            "int8 weight quantization of the Gated DeltaNet layout "
            "(`olmo_hybrid`) is not built: its mixers are stacked per kind "
            "(`attn`) and have no contraction table here")
    if "dense_layers" in params or cfg.shared_intermediate_size:
        raise NotImplementedError(
            "int8 weight quantization of a tree with a dense lead or shared "
            "experts is not built: `dense_layers` and the shared branch "
            "(ws_g, ws_u, ws_d) have no contraction table here")
    contract = dict(_CONTRACT)
    if cfg.is_moe:
        contract.update(_CONTRACT_MOE)
    layers = dict(params["layers"])
    for name, axes in contract.items():
        if name in layers:
            layers[name] = quantize_array(layers[name], axes)
    out: Params = {
        "embed": quantize_array(params["embed"], (1,)),
        "final_norm": params["final_norm"],
        "layers": layers,
    }
    if "lm_head" in params:
        out["lm_head"] = quantize_array(params["lm_head"], (0,))  # [H, V]
    return out


def param_bytes(params: Params) -> int:
    """Stored bytes (int8 + scales) — the decode step's weight traffic."""
    return sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(params)
    )
