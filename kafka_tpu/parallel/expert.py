"""Expert parallelism: MoE expert shards over the "ep" mesh axis.

No Llama checkpoint in the registry is MoE, but the mesh reserves the axis
(SURVEY §2.2: "design the mesh axes so it can be added") — this module
makes the axis real infrastructure rather than a name: a functional
top-k-routed MoE MLP whose expert dimension shards over "ep", validated
against the dense reference computation on the virtual mesh.

Design (the standard inference EP shape):

* experts are stacked [E, ...]; rank r of the ep axis holds experts
  [r*E/ep, (r+1)*E/ep);
* tokens stay replicated; every rank computes the contribution of ITS
  experts for the tokens routed to them (dense dispatch via the routing
  weights, zero for tokens routed elsewhere) and a `psum` combines —
  collectives stay on ICI, no token-permutation bookkeeping.  This is the
  capacity-unlimited formulation: exact, simple, and bandwidth-fine at
  serving batch sizes; switch to all_to_all token dispatch when expert
  count × batch makes dense dispatch the bottleneck.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.ffn import _routing_weights as _canonical_routing_weights

Params = Dict[str, jnp.ndarray]


def init_moe_params(
    key: jax.Array, num_experts: int, hidden: int, ffn: int, dtype=jnp.float32
) -> Params:
    """[E, ...]-stacked SwiGLU experts + router."""
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def norm(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5).astype(dtype)

    return {
        "router": norm(k1, (hidden, num_experts), hidden),
        "wg": norm(k2, (num_experts, hidden, ffn), hidden),
        "wu": norm(k3, (num_experts, hidden, ffn), hidden),
        "wd": norm(k4, (num_experts, ffn, hidden), ffn),
    }


def _routing_weights(x: jnp.ndarray, router: jnp.ndarray, top_k: int):
    """Canonical exact-top-k routing lives in models/ffn.py (the served
    model); reused here so the two cannot drift."""
    return _canonical_routing_weights(x, router, top_k)


def moe_mlp_reference(x: jnp.ndarray, params: Params, top_k: int = 2):
    """Dense single-device reference: x [T, H] -> [T, H]."""
    w = _routing_weights(x, params["router"], top_k)  # [T, E]
    g = jnp.einsum("th,ehf->tef", x, params["wg"])
    u = jnp.einsum("th,ehf->tef", x, params["wu"])
    y = jnp.einsum("tef,efh->teh", jax.nn.silu(g) * u, params["wd"])
    return jnp.einsum("te,teh->th", w, y)


def moe_mlp_sharded(
    mesh: Mesh, x: jnp.ndarray, params: Params, top_k: int = 2
) -> jnp.ndarray:
    """Expert-sharded MoE MLP over the "ep" axis; matches the reference."""

    def per_shard(x_, router, wg, wu, wd):
        # router replicated -> identical routing decisions on every rank
        w = _routing_weights(x_, router, top_k)  # [T, E_global]
        e_local = wg.shape[0]
        rank = lax.axis_index("ep")
        w_local = lax.dynamic_slice_in_dim(w, rank * e_local, e_local, 1)
        g = jnp.einsum("th,ehf->tef", x_, wg)
        u = jnp.einsum("th,ehf->tef", x_, wu)
        y = jnp.einsum("tef,efh->teh", jax.nn.silu(g) * u, wd)
        local = jnp.einsum("te,teh->th", w_local, y)
        return lax.psum(local, "ep")

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(), P(), P("ep"), P("ep"), P("ep")),
        out_specs=P(),
    )
    return fn(x, params["router"], params["wg"], params["wu"], params["wd"])


def shard_moe_params(params: Params, mesh: Mesh) -> Params:
    specs = {
        "router": P(),
        "wg": P("ep"), "wu": P("ep"), "wd": P("ep"),
    }
    return {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in params.items()
    }
