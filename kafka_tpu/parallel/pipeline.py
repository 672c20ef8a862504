"""Pipeline parallelism: layer stages sharded over the "pp" mesh axis.

The model's parameters are layer-stacked ([L, ...] per tensor,
models/llama.py) precisely so the leading axis can be cut into pipeline
stages: rank s of the pp axis holds layers [s*L/pp, (s+1)*L/pp) and
activations hop rank→rank+1 over `lax.ppermute` (ICI within a slice, DCN
across slices — the axis order in parallel/mesh.py puts pp outermost for
exactly that reason).

Scope and honesty: this is *sequential* pipeline execution — each stage
computes while the others idle, activations ppermute forward, and the last
stage holds the logits.  That is the correct latency shape for single-token
decode (stages are inherently sequential for one token) and it delivers
PP's main serving win: a model whose weights exceed one device's HBM runs
with 1/pp of the layers per device.  Microbatched prefill overlap (the
throughput optimization trainers need) is deliberately not implemented —
it changes nothing about parameter placement and can be layered onto this
stage structure later.

Composes with TP: give the mesh both axes (pp outer, tp inner) and the
per-stage weights follow the usual Megatron specs within each stage.

Two entry points:

* `pp_forward` — uncached forward (numerics reference, offline scoring).
* `pp_forward_paged` — the *serving* path: same stage structure but every
  stage reads/writes its local shard of the engine's paged KV pool
  ([L, SLOTS, Hkv*D] with L sharded over "pp", kv heads over "tp"; the
  shard is the stage's layer-scan carry, addressed by local layer index
  exactly as models/llama.py forward addresses the whole pool), so
  the continuous-batching engine (runtime/engine.py) drives prefill and
  decode through pipeline stages exactly as it does TP — each device
  holds 1/pp of the weights AND 1/pp of the KV cache.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import pcast
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.cache import PagedView
from ..models.config import ModelConfig
from ..models.ffn import _mlp_block
from ..models.mixers.gqa import WindowedPathError, _attention_block
from ..models.quant import Params
from ..ops.norms import rms_norm
from ..ops.rope import rope_cos_sin, rope_frequencies


def pp_param_specs(cfg: ModelConfig, mesh: Mesh) -> Params:
    """PartitionSpecs with the stacked layer axis sharded over "pp".

    Embedding/head/final norm are replicated (they live on the first/last
    stages logically; replication keeps the spec simple and they are a few
    percent of weights).  Within a stage, heads/hidden shard over "tp"
    exactly as in sharding.param_specs.
    """
    from .sharding import _kv_axis

    kv = _kv_axis(cfg, mesh)
    specs: Params = {
        "embed": P(),
        "final_norm": P(),
        "layers": {
            "ln_attn": P("pp", None),
            "ln_mlp": P("pp", None),
            "wq": P("pp", None, "tp", None),
            "wk": P("pp", None, kv, None),
            "wv": P("pp", None, kv, None),
            "wo": P("pp", "tp", None, None),
            "wg": P("pp", None, "tp"),
            "wu": P("pp", None, "tp"),
            "wd": P("pp", "tp", None),
        },
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


def shard_params_pp(params: Params, cfg: ModelConfig, mesh: Mesh) -> Params:
    specs = pp_param_specs(cfg, mesh)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


def _check_pp_divisibility(cfg: ModelConfig, pp: int, tp: int) -> None:
    if cfg.layer_types:
        # the stage body below is ONE homogeneous layer with one rotary
        # table: it would run a sliding-window layer as a global one
        raise WindowedPathError(
            "pp stage sharding (parallel/pipeline.py) scans one layer body "
            "and has no form for a layer pattern "
            f"({list(cfg.layer_period)})")
    if cfg.num_layers % pp:
        raise ValueError(
            f"num_layers {cfg.num_layers} not divisible by pp={pp}"
        )
    if tp > 1 and (cfg.num_heads % tp or cfg.num_kv_heads % tp):
        raise ValueError(
            f"pp x tp compose needs tp={tp} to divide heads "
            f"({cfg.num_heads}) and kv heads ({cfg.num_kv_heads})"
        )


def kv_pool_spec_pp(cfg: ModelConfig, mesh: Mesh) -> P:
    """[L, SLOTS, Hkv*D] pool with layers staged over "pp": each device
    caches only its own stage's layers (and its tp shard of heads) — the
    KV memory follows the weights, which is what lets a model bigger than
    one device's HBM actually *serve*."""
    from .sharding import _kv_axis

    return P("pp", None, _kv_axis(cfg, mesh))


def _embed_and_rope(params: Params, cfg: ModelConfig, token_ids, positions):
    x = params["embed"][token_ids].astype(cfg.activation_dtype)
    cos, sin = rope_cos_sin(positions, rope_frequencies(cfg))
    return x, cos, sin


def _logits_head(params: Params, cfg: ModelConfig, h: jnp.ndarray):
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    if cfg.tie_word_embeddings:
        return jnp.einsum(
            "bsh,vh->bsv", h, params["embed"],
            preferred_element_type=jnp.float32,
        )
    return jnp.einsum(
        "bsh,hv->bsv", h, params["lm_head"],
        preferred_element_type=jnp.float32,
    )


def pp_forward_paged(
    params: Params,
    cfg: ModelConfig,
    token_ids: jnp.ndarray,
    positions: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    paged,
    mesh: Mesh,
):
    """Stage-sharded forward against the paged KV pool (the serving path).

    Same index-plan contract as models.forward's paged mode: `paged` is a
    runtime PagedView whose write_idx/read_idx/kv_valid arrays address the
    flat slot axis; k_pool/v_pool are [L, SLOTS, Hkv*D] placed per
    `kv_pool_spec_pp`.  Returns (logits [B, S, V] f32, k_pool', v_pool').

    Stage s computes its layers (its local pool shard [L/pp, SLOTS, HD/tp]
    rides the layer scan as carry; each layer scatters its new rows into
    it and gathers its window from it at the layer's local offset),
    the hidden state ppermutes to stage s+1, and the last stage's output
    is broadcast for the (replicated) logits head.  Attention inside a
    stage is the XLA gather formulation with heads tp-local and explicit
    psums after the row-parallel projections — identical math to the
    engine's TP path, so outputs are token-exact vs a single device.
    """
    pp = mesh.shape.get("pp", 1)
    tp = mesh.shape.get("tp", 1)
    _check_pp_divisibility(cfg, pp, tp)

    x, cos, sin = _embed_and_rope(params, cfg, token_ids, positions)

    def per_shard(layer_params, kp, vp, h, cos, sin, pos,
                  write_idx, read_idx, kv_positions, kv_valid):
        rank = lax.axis_index("pp")

        def tp_reduce(t):
            return lax.psum(t, "tp") if tp > 1 else t

        # Same index-plan contract as the engine's TP path, minus the
        # pallas/ring fields (page_table=None selects _attention_block's
        # XLA gather branch — the only backend legal on a pp mesh).
        paged_local = PagedView(write_idx, read_idx, kv_positions, kv_valid)

        def run_stage(operand):
            # the stage's pool shard is scan carry and a layer addresses
            # its part by its LOCAL index (models/llama.py's convention)
            def body(carry, scanned):
                hh, kc, vc = carry
                lp, layer = scanned
                attn_in = rms_norm(hh, lp["ln_attn"], cfg.rms_norm_eps)
                attn_out, kc, vc = _attention_block(
                    attn_in, lp, cfg, cos, sin, pos, kc, vc,
                    None, None, paged_local, None, layer,
                )
                hh = hh + tp_reduce(attn_out)
                mlp_in = rms_norm(hh, lp["ln_mlp"], cfg.rms_norm_eps)
                hh = hh + tp_reduce(_mlp_block(mlp_in, lp))
                return (hh, kc, vc), None

            return lax.scan(
                body, operand, (layer_params, jnp.arange(kp.shape[0])))[0]

        h = pcast(h, ("pp", "tp"), to="varying")
        for s in range(pp):  # sequential stages; only rank s computes
            h, kp, vp = lax.cond(
                rank == s, run_stage, lambda op: op, (h, kp, vp)
            )
            if s + 1 < pp:
                h = lax.ppermute(h, "pp", [(s, s + 1)])
        # broadcast the last stage's hidden state (see pp_forward)
        tp_rank = lax.axis_index("tp")
        keep = (rank == pp - 1) & (tp_rank == 0)
        h = lax.psum(jnp.where(keep, h, jnp.zeros_like(h)), ("pp", "tp"))
        return h, kp, vp

    layer_specs = pp_param_specs(cfg, mesh)["layers"]
    pool_spec = kv_pool_spec_pp(cfg, mesh)
    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(layer_specs, pool_spec, pool_spec,
                  P(), P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(), pool_spec, pool_spec),
    )
    h, k_pool, v_pool = fn(
        params["layers"], k_pool, v_pool, x, cos, sin, positions,
        paged.write_idx, paged.read_idx, paged.kv_positions, paged.kv_valid,
    )
    return _logits_head(params, cfg, h), k_pool, v_pool


def pp_forward(
    params: Params,
    cfg: ModelConfig,
    token_ids: jnp.ndarray,
    positions: jnp.ndarray,
    mesh: Mesh,
) -> jnp.ndarray:
    """Uncached forward with layers stage-sharded over "pp".

    Returns logits [B, S, V], numerically identical to models.forward on a
    single device (tested).  Params must be placed by shard_params_pp.
    """
    pp = mesh.shape.get("pp", 1)
    tp = mesh.shape.get("tp", 1)
    _check_pp_divisibility(cfg, pp, tp)

    def per_shard(layer_params, x, cos, sin, pos):
        # layer_params: this rank's [L/pp, ...] stage slice, heads/hidden
        # additionally tp-sharded (each device holds 1/(pp*tp) of layer
        # weights — the HBM point of the composition).  Inside shard_map
        # the tp collectives are explicit: the row-parallel projections
        # (wo over heads, wd over ffn) produce partial sums that psum over
        # "tp"; q/kv head shards stay aligned because both split into
        # contiguous blocks of the same rank order.
        rank = lax.axis_index("pp")

        def tp_reduce(t):
            return lax.psum(t, "tp") if tp > 1 else t

        def run_stage(h):
            def body(h, lp):
                attn_in = rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps)
                attn_out, _, _ = _attention_block(
                    attn_in, lp, cfg, cos, sin, pos, None, None, None, None
                )
                h = h + tp_reduce(attn_out)
                mlp_in = rms_norm(h, lp["ln_mlp"], cfg.rms_norm_eps)
                return h + tp_reduce(_mlp_block(mlp_in, lp)), None

            out, _ = lax.scan(body, h, layer_params)
            return out

        # the replicated input becomes rank-varying the moment it meets the
        # stage- and head-sharded weights; cast up front so scan/cond
        # carries type-check (same vma dance as ring_attention)
        h = pcast(x, ("pp", "tp"), to="varying")
        for s in range(pp):  # sequential stages; only rank s computes
            h = lax.cond(rank == s, run_stage, lambda v: v, h)
            if s + 1 < pp:
                h = lax.ppermute(h, "pp", [(s, s + 1)])
        # only the final stage holds the result (identical across tp after
        # the per-layer psums); a psum of the value masked down to exactly
        # ONE (pp, tp) rank broadcasts it everywhere and lets shard_map
        # prove the replicated out_spec
        tp_rank = lax.axis_index("tp")
        keep = (rank == pp - 1) & (tp_rank == 0)
        h = lax.psum(
            jnp.where(keep, h, jnp.zeros_like(h)), ("pp", "tp")
        )
        return h

    x, cos, sin = _embed_and_rope(params, cfg, token_ids, positions)

    layer_specs = pp_param_specs(cfg, mesh)["layers"]
    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(layer_specs, P(), P(), P(), P()),
        out_specs=P(),
    )
    h = fn(params["layers"], x, cos, sin, positions)
    return _logits_head(params, cfg, h)
