"""Decoder-only transformer in pure functional JAX: `forward` -- the embedding,
the layer loop (leading dense layers, lead layers, the scan over layers or
over whole periods of a layer pattern) and the head -- over the MIXER each
kind of layer takes (`mixers.MIXERS[cfg.mixer_of(kind)]`: mixers/gqa.py,
latent.py + index.py, state.py) and the feed-forward half (models/ffn.py);
the widened residual stream is models/residual.py's, the trees
models/init_params.py's, the cache forms and state slots models/cache.py's,
and `phi4flash`'s decoder is models/hybrid.forward behind this same entry.

* **Stacked layer parameters + `lax.scan`** -- all L layers' weights are one
  pytree of [L, ...] arrays and the layer body is scanned: one compiled body
  instead of L inlined copies, and the leading axis is what pipeline stages
  split.  Pure functions (`init_params`, `forward`), no module framework; the
  engine jits / shard_maps them with explicit sharding rules
  (parallel/sharding.py).  BSHD activations, bf16 params and activations, f32
  norms and softmax; attention through ops.attention or the Pallas kernels.
* **One mixer signature** -- `mix(x, lp, ctx, kc, vc, layer, kind) -> (out,
  kc, vc)` (mixers/__init__.py).  A mixer owns its part of the cache dict;
  the layer body compares no kind.
* **Layer patterns** -- a config whose layers alternate kinds
  (`ModelConfig.layer_types`) is scanned over whole PERIODS of the pattern:
  the weights stay stacked [L, ...], the scan runs over the index of each
  period's first layer, and its body is the p layers of one period unrolled,
  each indexing its own weights, each kind its own code with its own static
  window and rotary table.  A period of one is the plain scan.  Leading dense
  layers (`cfg.first_k_dense`, a stacked tree of their own) run unrolled
  ahead of the scan; both index the one stacked pool by absolute layer.
* **The stacked cache is scan CARRY, never a scanned input**: a layer
  addresses its part by index (models/cache.py `_layer_view`).  Scanned (xs
  in, ys out), XLA sliced every layer's whole pool out and wrote it back on
  every pass: a third of a decode step's device time (PERF.md, PR 25).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.norms import rms_norm
from ..ops.rope import kind_frequencies, rope_cos_sin, rope_frequencies
from .cache import (
    INDEX, HybridPathError, KVCache, PagedView, StatePlan, _read_state,
    _write_state,
)
from .config import GLOBAL, MOE, ModelConfig
from .ffn import (
    _mlp_block, _moe_block, expert_leaves, experts_int8, moe_dispatch_form,
)
from .hybrid import forward as hybrid_forward
from .init_params import init_params
from .mixers import MIXERS, MixContext
from .quant import Params, QTensor
from .residual import _hc_in, _hc_out, _hc_rows

# (benchmarks/ imports KVCache, PagedView and init_params from this module)
__all__ = ["KVCache", "PagedView", "forward", "init_kv_cache", "init_params"]


def init_kv_cache(cfg: ModelConfig, batch: int, capacity: int, dtype=None) -> KVCache:
    dtype = dtype or cfg.activation_dtype
    if cfg.has_state:
        raise HybridPathError(
            "a decoder with a recurrent state has no contiguous cache: rows "
            "go through the paged pool, the state through its slots")
    if cfg.by_kind:
        # per kind of layer, as the paged pool (runtime/kv_cache.py)
        def rows(n, width):
            return jnp.zeros((n, batch, capacity, 1, width), dtype)

        k, v = {}, {}
        for kind in cfg.kinds:
            g, n = cfg.geometry_of(kind), cfg.layers_of(kind)
            k[kind] = rows(n, g.kv_lora_rank)
            v[kind] = rows(n, g.qk_rope_head_dim)
            if cfg.has_indexer(kind):
                v[INDEX] = rows(n, cfg.index_head_dim)
        return KVCache(k=k, v=v)
    if cfg.is_latent:
        # one "head": k holds the latent c~, v the roped k_r (no padding:
        # nothing DMAs this cache by lane tile)
        lead = (cfg.num_layers, batch, capacity, 1)
        return KVCache(k=jnp.zeros(lead + (cfg.kv_lora_rank,), dtype),
                       v=jnp.zeros(lead + (cfg.qk_rope_head_dim,), dtype))
    shape = (cfg.num_layers, batch, capacity, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def forward(
    params: Params,
    cfg: ModelConfig,
    token_ids: jnp.ndarray,
    positions: jnp.ndarray,
    kv_cache: Optional[KVCache] = None,
    kv_valid: Optional[jnp.ndarray] = None,
    cache_positions: Optional[jnp.ndarray] = None,
    paged: Optional[PagedView] = None,
    mesh=None,
    embed_override: Optional[jnp.ndarray] = None,
    override_on: Optional[jnp.ndarray] = None,
    expert_reads: bool = False,
) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """Run the decoder.

    token_ids, positions: [B, S] int32.
    kv_cache: optional KVCache. Contiguous form: k/v [L, B, C, Hkv, D],
        new k/v written at `cache_positions` (default `positions`), attention
        over the whole cache gated by `kv_valid` [B, C]. Paged form (when
        `paged` is given): k/v [L, TOTAL_SLOTS, Hkv*D] (heads merged into
        the minor axis, runtime/kv_cache.py), reads/writes follow the
        PagedView index plan.
    embed_override [B, S, H] + override_on [B, S] bool: positions whose
        input embedding is REPLACED (image patches entering as soft-prompt
        tokens, models/vision.py; the reference forwarded images to remote
        vision models, src/llm/portkey.py:276).
    Returns (logits [B, S, vocab] float32, updated cache or None), and with
    `expert_reads` (a routed model's own layer tree) a third, i32 [3] over
    this pass's routed layers: the held experts whose weights they read
    (what token dispatch leaves unread) and the real rows' picks that fell on
    an expert held here, both summed a layer (`_moe_block`'s `count_picks`),
    and all their picks, rows x top-k x routed layers.

    A model with a recurrent state (`cfg.has_state`): its paged pool carries
    the state (`kv_cache.v` a dict), `paged.state` says which slots, and a
    paged prefill returns its lanes' last real rows only, logits [B, 1,
    vocab].  `phi4flash`'s decoder (`cfg.hybrid_decoder`) is
    models/hybrid.forward behind this same entry; every other decoder with a
    state runs here, its mixers taken from the table by kind.
    """
    plan = None
    if cfg.has_state:
        if mesh is not None and mesh.size > 1 or embed_override is not None:
            raise HybridPathError(
                "a decoder with a recurrent state runs on one device a "
                "replica, text only")
        if cfg.hybrid_decoder:
            return hybrid_forward(params, cfg, token_ids, positions,
                                  kv_cache, paged)
        if kv_cache is not None and (paged is None or paged.state is None
                                     or paged.page_table is None):
            raise HybridPathError(
                "a decoder with a recurrent state has no contiguous cache "
                "and no paged plan without a page table and a StatePlan: "
                "rows go through the paged pool, the state through its "
                "slots")
        plan = paged.state if paged is not None else StatePlan(
            lens=jnp.full(token_ids.shape[:1], token_ids.shape[1],
                          jnp.int32))
    with jax.named_scope("embed"):
        embed = params["embed"]
        if isinstance(embed, QTensor):
            # per-row dequant of only the looked-up rows (scale is [V, 1])
            x = (
                embed.q[token_ids].astype(cfg.activation_dtype)
                * embed.s[token_ids].astype(cfg.activation_dtype)
            )
        else:
            x = embed[token_ids].astype(cfg.activation_dtype)
        if embed_override is not None:
            x = jnp.where(
                override_on[..., None],
                embed_override.astype(cfg.activation_dtype), x,
            )
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        # one rotary table per kind of layer, built once per forward pass;
        # each layer takes its kind's (a config without a pattern has one)
        lead, period = cfg.pattern
        if cfg.layer_types or cfg.rope_by_kind:
            rope = {kind: rope_cos_sin(positions, *kind_frequencies(cfg, kind))
                    for kind in cfg.kinds
                    if cfg.mixer_of(kind) is not None
                    and MIXERS[cfg.mixer_of(kind)].positional}
        else:
            inv_freq = rope_frequencies(cfg)
            rope = {GLOBAL: rope_cos_sin(positions, inv_freq)}
        for kind in cfg.unrotated_kinds:
            rope[kind] = (None, None)
        if cfg.hc_mult > 1:
            # the widened residual stream: the embedding row in every one of
            # a token's n rows ([B, S, n * C]: `_hc_rows`)
            x = jnp.tile(x, (1, 1, cfg.hc_mult))

    # Where the routed blocks dispatch by token (moe_dispatch_form: this
    # pass's rows), the expert leaves stay out of what is sliced a layer:
    # `_moe_block` is handed the stack and the layer's index in it (a third
    # entry of `scanned`), and a program that keeps the dense form is traced
    # as it always was.  (int8 experts are dequantized a layer, from
    # their slices.)
    # (a one-sublayer pattern stacks the routed feed-forward's leaves per
    # kind, `ffn`: the expert stack is then its MOE layers')
    sharded = mesh is not None and mesh.size > 1
    layers, experts, ffn = params["layers"], None, params.get("ffn", {})
    routed_stack = ffn[MOE] if ffn else layers
    if (cfg.is_moe and moe_dispatch_form(
            token_ids.shape[0] * token_ids.shape[1], cfg.num_experts,
            cfg.num_experts_per_tok, sharded,
            cfg.num_router_experts) == "token"
            and not experts_int8(routed_stack)):
        experts = {n: routed_stack[n] for n in expert_leaves(routed_stack)}
        routed_stack = {n: a for n, a in routed_stack.items()
                        if n not in experts}
        if ffn:
            ffn = {MOE: routed_stack}
        else:
            layers = routed_stack

    def indexed(index):
        """`scanned`'s third entry, the layer's index() in the expert stack
        (nothing, and not an op traced, where the block keeps `lp`'s)."""
        return () if experts is None else (index(),)

    # what the pass fixes for every layer's mixer (mixers.MixContext); the
    # slot accessors as THIS module names them
    ctx = MixContext(cfg, rope, positions, kv_valid, cache_positions, paged,
                     mesh, plan, _read_state, _write_state)

    # The stacked caches are CARRY (module docstring): the scan slices only
    # the layer's weights.  Every op of the layer body sits under a leaf
    # scope (residual adds included), so what a device trace shows under
    # `layers` alone is the scan's own slicing of its stacked inputs.
    # A kind runs the halves it HAS (`cfg.mixer_of`, `cfg.has_ffn`): in a
    # one-sublayer pattern a layer is a mixer or a feed-forward under its
    # one norm ("ln") and one residual add, and no op of the absent half is
    # traced.
    # Where a sublayer's norm sits (`cfg.norm_position`): on its INPUT under
    # the norm's own scope, or ("post", the reordered norm) on its OUTPUT,
    # inside the scope of the add it precedes (residual._hc_out) with the
    # sublayer reading the stream as it is and no input norm traced.
    post = cfg.norm_position == "post"

    def norm_in(u, w, scope):
        if post:
            return u
        with jax.named_scope(scope):
            return rms_norm(u, w, cfg.rms_norm_eps)

    def norm_out(w):
        return (w, cfg.rms_norm_eps) if post else None

    def layer_body(carry, scanned, kind=GLOBAL, routed=cfg.is_moe):
        h, kc, vc, tally = carry
        lp, layer, *slot = scanned
        lone, r = cfg.lone_layers, cfg.residual_multiplier
        if cfg.mixer_of(kind) is not None:
            mixer = MIXERS[cfg.mixer_of(kind)]
            u, maps = _hc_in(h, lp, "attn", cfg)
            w = lp["ln" if lone else "ln_attn"]
            # (`layer` is the layer's index in the caches its mixer holds:
            # among its kind where the leaves are per kind, absolute
            # elsewhere)
            attn_out, kc, vc = mixer.mix(norm_in(u, w, "attn_norm"), lp, ctx,
                                         kc, vc, layer, kind)
            h = _hc_out(h, attn_out, maps, mixer.scope, r, norm_out(w))
        if not cfg.has_ffn(kind):
            return (h, kc, vc, tally), None
        u, maps = _hc_in(h, lp, "mlp", cfg)
        w = lp["ln" if lone else "ln_mlp"]
        mlp_in = norm_in(u, w, "mlp_norm")
        if routed:
            ffn_out, read = _moe_block(
                mlp_in, lp, cfg, None if paged is None else paged.chunk_len,
                sharded, (experts, slot[0]) if slot else None,
                count_picks=tally is not None)
            if tally is not None:
                tally = tally + read
            h = _hc_out(h, ffn_out, maps, "moe_experts", r, norm_out(w))
        else:
            with jax.named_scope("mlp"):
                ffn_out = _mlp_block(mlp_in, lp,
                                     multipliers=cfg.mlp_multipliers)
            h = _hc_out(h, ffn_out, maps, "mlp", r, norm_out(w))
        return (h, kc, vc, tally), None

    def at(stacked, i, static: bool):
        """Layer i's leaves of a stacked tree: `a[i]` where i is static,
        one dynamic slice a leaf where it is the scan's."""
        if static:
            return jax.tree.map(lambda a: a[i], stacked)
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, i, axis=0, keepdims=False), stacked)

    def layer_of(stack: str, i, layer, kind, static: bool, nth=None):
        """(leaves, cache index) of layer `layer` (absolute), the i-th of
        `params[stack]`.  A by_kind model takes its attention leaves from
        its kind's own stack and indexes its kind's caches, both at `nth`,
        the layer's place among its kind; so with the feed-forward leaves
        of a kind that has a stack of its own (`ffn`), the expert stack
        among them."""
        routed = stack == "layers"
        lp = at(layers if routed else params[stack], i, static)
        if not cfg.kind_leaves:
            return (lp, layer) + (indexed(lambda: i) if routed else ())
        for own in (params["attn"].get(kind), ffn.get(kind)):
            if own is not None:
                lp = {**lp, **at(own, nth, static)}
        return (lp, nth) + (
            indexed(lambda: nth if ffn else i) if routed else ())

    def period_body(carry, first):
        """One whole period of the pattern, from absolute layer `first`: its
        layers unrolled, each kind its own code with its own static window
        and rotary table.
        Each layer's weights are indexed out of the stacked [L, ...] arrays
        at `first + j`, one dynamic slice a leaf exactly as the plain scan
        takes them: scanning over a [L/p, p, ...] view instead made XLA
        materialise the whole period's weights every iteration (3.2 GB of
        copies a period at Mellum2's widths, rehearsed for the v5e)."""
        ahead = n_dense + lead
        # (no arithmetic on `first` where there is nothing ahead: the
        # program of a model without dense or lead layers stays as it was)
        stacked = first - n_dense if n_dense else first
        t = (first - ahead) // len(period) if cfg.kind_leaves else None
        for j, kind in enumerate(period):
            if cfg.kind_leaves:
                nth = (before(ahead, kind) + t * period.count(kind)
                       + period[:j].count(kind))
                scanned = layer_of("layers", stacked + j, first + j, kind,
                                   False, nth)
            else:
                # (the index summed anew for every leaf, as it always was:
                # the lowered text of a patterned GQA model does not move)
                lp = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, stacked + j, axis=0, keepdims=False),
                    layers)
                scanned = (lp, first + j) + indexed(lambda: stacked + j)
            carry, _ = layer_body(carry, scanned, kind)
        return carry, None

    def before(layer: int, kind: str) -> int:
        # (a model without `layer_types` has one kind: every layer is of it)
        return sum(cfg.kind_of(i) == kind for i in range(layer))

    with jax.named_scope("layers"):
        kc, vc = (None, None) if kv_cache is None else kv_cache
        num_layers = jax.tree.leaves(params["layers"])[0].shape[0]
        n_dense = 0
        # (the tally of experts read and of picks rides the carry: None, no
        # leaf, unless `expert_reads`)
        carry = (x, kc, vc,
                 jnp.zeros((2,), jnp.int32) if expert_reads else None)
        if "dense_layers" in params:
            # leading dense layers: a stacked tree of another shape, run
            # ahead of the scan over the routed layers, which count on from
            # them.  Unrolled, not a scan of their own: one innermost loop a
            # forward pass is what a device trace counts passes by.
            n_dense = jax.tree.leaves(params["dense_layers"])[0].shape[0]
            for i in range(n_dense):
                kind = cfg.kind_of(i)
                carry, _ = layer_body(
                    carry, layer_of("dense_layers", i, i, kind, True,
                                    before(i, kind)),
                    kind, routed=False)
        if len(period) > 1 and num_layers - lead == len(period):
            # ONE whole period: XLA removes a one-trip loop anyway, and
            # nested in the fused program's scan over steps it then copied
            # every slice the body had taken at the scan's index out of the
            # stacked leaves (15 x 480 MB of expert weights: my chip run 1,
            # PR 33).  Unrolled here, each layer's leaves are static slices
            # of the leading axis: views.
            lead = num_layers
        for i in range(lead):
            # the layers that stand alone ahead of whole periods, unrolled
            # beside the dense ones
            kind = cfg.kind_of(n_dense + i)
            carry, _ = layer_body(
                carry, layer_of("layers", i, n_dense + i, kind, True,
                                before(n_dense + i, kind)), kind)
        if len(period) == 1 and not (cfg.kind_leaves or lead):
            layer_ids = (jnp.arange(n_dense, n_dense + num_layers) if n_dense
                         else jnp.arange(num_layers))
            carry, _ = jax.lax.scan(
                partial(layer_body, kind=period[0]),
                carry,
                (layers, layer_ids) + indexed(
                    lambda: jnp.arange(num_layers)),
            )
        elif lead < num_layers:
            p = len(period)
            if (num_layers - lead) % p:
                raise ValueError(
                    f"{num_layers} stacked layers are not {lead} and whole "
                    f"periods of the {p}-layer pattern")
            carry, _ = jax.lax.scan(
                period_body, carry,
                jnp.arange(n_dense + lead, n_dense + num_layers, p))
        x, kc, vc, tally = carry
        new_cache = None if kv_cache is None else KVCache(k=kc, v=vc)
        if expert_reads:
            # all the pass's picks, counted ONCE: its real rows (the view's
            # `chunk_len`: a decode step's active lanes) x top-k x routed
            # layers; where the experts are held whole every one of them is
            # a held pick, and no routed layer counted any
            rows = x.shape[0] * x.shape[1]
            if paged is not None and paged.chunk_len is not None:
                rows = jnp.sum(jnp.clip(jnp.broadcast_to(
                    paged.chunk_len, x.shape[:1]), 0, x.shape[1]))
            picks = jnp.asarray(
                rows * (cfg.num_experts_per_tok * cfg.routed_layers),
                jnp.int32)
            tally = jnp.stack([
                tally[0], tally[1] if cfg.num_experts_routed else picks,
                picks])

    with jax.named_scope("head"):
        if plan is not None and paged is not None and x.shape[1] > 1:
            # a prefill launch of a model with a state: each lane's last
            # real row is all anybody reads (as models/hybrid.forward)
            last = jnp.clip(plan.lens - 1, 0, x.shape[1] - 1)
            x = jnp.take_along_axis(x, last[:, None, None], axis=1)
        if cfg.hc_mult > 1:
            # the stream collapses to the sum of a token's rows
            x = sum(_hc_rows(x, cfg.hc_mult)).astype(x.dtype)
        logits = _logits_head(x, params, cfg)
    if expert_reads:
        return logits, new_cache, tally
    return logits, new_cache


def _logits_head(x: jnp.ndarray, params: Params,
                 cfg: ModelConfig) -> jnp.ndarray:
    """Final RMSNorm + vocabulary projection: [B, S, H] -> f32 logits."""
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    # bf16 matmul with f32 accumulation: the MXU-native mode. Casting the
    # [V, H] table to f32 would stream an extra ~1 GB per step through HBM
    # on a 128k vocab for no accuracy the f32 accumulator doesn't already
    # provide.
    # Int8 heads: the matmul streams the int8 table upcast to bf16 and the
    # per-vocab-row scale applies to the f32 OUTPUT — exact (scales are
    # per output channel) and cheaper than dequantizing the [V, H] table.
    head, eq = ((params["embed"], "bsh,vh->bsv") if cfg.tie_word_embeddings
                else (params["lm_head"], "bsh,hv->bsv"))  # [V, H] | [H, V]
    if isinstance(head, QTensor):
        logits = jnp.einsum(
            eq, x, head.q.astype(x.dtype), preferred_element_type=jnp.float32,
        ) * head.s.reshape(1, 1, -1)
    else:
        logits = jnp.einsum(eq, x, head, preferred_element_type=jnp.float32)
    if cfg.lm_head_multiplier != 1.0:
        logits = logits * cfg.lm_head_multiplier
    return logits
