"""Random-init parameters (layer-stacked), one initialiser a tree.  Serving
loads checkpoints; random init is for tests, the benchmark (whose weights ARE
these seeded streams: no draw may move) and the planner, which counts a
configuration's bytes off the abstract tree (`jax.eval_shape`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .config import CONV, DELTA, GLOBAL, MAMBA2, MOE, ModelConfig
from .hybrid import init_params as init_hybrid_params
from .mixers.state import ssd_mup_vector
from .quant import Params
from .residual import HC_SITES


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init parameters (layer-stacked). Serving loads checkpoints
    instead; random init exists for tests and micro-benchmarks."""
    dtype = dtype or cfg.activation_dtype
    if cfg.hybrid_decoder:
        return init_hybrid_params(cfg, key, dtype)
    if cfg.by_kind:
        return _init_kind_params(cfg, key, dtype)
    if cfg.lone_layers:
        return _init_lone_params(cfg, key, dtype)
    if cfg.lead_tree:
        # a tree and a random stream of its own: the stream below is what
        # every other configuration's seeded weights come from
        return _init_lead_tree_params(cfg, key, dtype)
    h, f, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    hq, hkv, L = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers
    keys = jax.random.split(key, 10)
    if cfg.ssd_heads:
        return _init_parallel_params(cfg, keys, dtype)

    def norm01(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * (fan_in**-0.5)).astype(dtype)

    layers: Params = {
        "ln_attn": jnp.ones((L, h), dtype),
        "ln_mlp": jnp.ones((L, h), dtype),
        "wq": norm01(keys[1], (L, h, hq, d), h),
        "wk": norm01(keys[2], (L, h, hkv, d), h),
        "wv": norm01(keys[3], (L, h, hkv, d), h),
        "wo": norm01(keys[4], (L, hq, d, h), hq * d),
    }
    if cfg.is_moe:
        # Mixtral-style MoE MLP: router [L, H, E] + E stacked SwiGLU
        # experts per layer (expert axis shards over "ep")
        E = cfg.num_experts
        layers["router"] = norm01(keys[9], (L, h, E), h)
        layers["wg"] = norm01(keys[5], (L, E, h, f), h)
        layers["wu"] = norm01(keys[6], (L, E, h, f), h)
        layers["wd"] = norm01(keys[7], (L, E, f, h), f)
    else:
        layers["wg"] = norm01(keys[5], (L, h, f), h)
        layers["wu"] = norm01(keys[6], (L, h, f), h)
        layers["wd"] = norm01(keys[7], (L, f, h), f)
    params: Params = {
        "embed": norm01(keys[0], (cfg.vocab_size, h), h),
        "final_norm": jnp.ones((h,), dtype),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm01(keys[8], (h, cfg.vocab_size), h)
    return params


def _init_parallel_params(cfg: ModelConfig, keys, dtype,
                          scaled: bool = True) -> Params:
    """Random weights of the parallel layout (`falcon_h1`): `init_params`'
    homogeneous stack with the SSD mixer's leaves beside the attention's in
    "layers" (`_ssd_block` names them): w_in [H, 2 d_ssm + 2 groups N +
    heads] (columns z | x | B | C | dt), the taps [L, conv] and their bias,
    A_log a head drawn log U(1, 16), dt_bias the inverse softplus of a step
    drawn log-uniform in [0.001, 0.1] (Mamba-2's own initialiser: a head's
    decay a row spreads over 0.9999 .. 0.2), D and the gated norm's weight
    spread around 1, w_out [d_ssm, H].

    THE MULTIPLIERS.  Every leaf that a muP multiplier scales is drawn at its
    fan-in standard deviation DIVIDED by that multiplier (`scaled`; the
    input projection's columns by their range's entry of `ssm_multipliers`
    too; the embedding at 1 / its multiplier, so that a row times it is of
    unit variance), so that scores, both mixers' outputs, the MLP's and the
    logits are of order 1 as every other preset's are.  At 1 / sqrt(fan_in) the
    published `key_multiplier` 0.011 would flatten every softmax to a mean
    over the keys and both mixers would enter the residual at 0.04 and 0.09:
    a check on the logits would be blind to a wrong mask, rotation or scan
    (a trained model's weights have grown against their multipliers)."""
    h, f, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    hq, hkv, L = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers
    H, P = cfg.ssd_heads, cfg.ssd_head_dim
    d_ssm, conv, taps = H * P, cfg.ssd_conv_dim, cfg.ssd_conv_kernel
    proj = d_ssm + conv + H
    gate_m, down_m = cfg.mlp_multipliers or (1.0, 1.0)

    @partial(jax.jit, static_argnums=(1, 2, 3))
    def norm01(k, shape, fan_in, mult=1.0):
        # one program a leaf: no float32 copy of a 0.8G-element leaf is held
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in**-0.5 / (mult if scaled else 1.0))).astype(dtype)

    def spread(k, shape, out_dtype=dtype):
        return (1.0 + 0.2 * jax.random.normal(k, shape, jnp.float32)
                ).astype(out_dtype)

    ks = jax.random.split(keys[9], 9)
    mup = ssd_mup_vector(cfg)
    w_in = norm01(ks[0], (L, h, proj), h, cfg.ssm_in_multiplier)
    if mup is not None and scaled:
        # (one program: no float32 copy of the leaf is held)
        w_in = jax.jit(lambda w: (w / mup).astype(dtype),
                       donate_argnums=0)(w_in)
    step = jnp.exp(jax.random.uniform(
        ks[5], (L, H), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
    a_in = cfg.attention_in_multiplier
    layers: Params = {
        "ln_attn": jnp.ones((L, h), dtype),
        "ln_mlp": jnp.ones((L, h), dtype),
        "wq": norm01(keys[1], (L, h, hq, d), h, a_in),
        "wk": norm01(keys[2], (L, h, hkv, d), h, a_in * cfg.key_multiplier),
        "wv": norm01(keys[3], (L, h, hkv, d), h, a_in),
        "wo": norm01(keys[4], (L, hq, d, h), hq * d,
                     cfg.attention_out_multiplier),
        "wg": norm01(keys[5], (L, h, f), h, gate_m),
        "wu": norm01(keys[6], (L, h, f), h),
        "wd": norm01(keys[7], (L, f, h), f, down_m),
        "w_in": w_in,
        "conv_w": norm01(ks[1], (L, taps, conv), taps),
        "conv_b": (0.1 * jax.random.normal(ks[2], (L, conv), jnp.float32)
                   ).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(
            ks[3], (L, H), jnp.float32, 1.0, 16.0)),
        "D": spread(ks[4], (L, H), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(step)),
        "ln_ssd": spread(ks[6], (L, d_ssm)),
        "w_out": norm01(ks[7], (L, d_ssm, h), d_ssm, cfg.ssm_out_multiplier),
    }
    params: Params = {
        # (a row times its multiplier of unit variance, as the blocks'
        # outputs are: the embedding then weighs in the residual stream)
        "embed": norm01(keys[0], (cfg.vocab_size, h), 1 if scaled else h,
                        cfg.embedding_multiplier),
        "final_norm": jnp.ones((h,), dtype),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm01(keys[8], (h, cfg.vocab_size), h,
                                   cfg.lm_head_multiplier)
    return params


# What `gdn_mixer` draws W_v and W_a at, times their fan-in deviation (see
# there).
GDN_V_GAIN = 0.03
GDN_A_GAIN = 0.05


def _scaled(fan_in, mult: float):
    """The fan-in under which a leaf that a multiplier scales is drawn: its
    deviation is fan_in ** -0.5 / mult (`_init_parallel_params`: THE
    MULTIPLIERS); `fan_in` itself where there is none."""
    return fan_in if mult == 1.0 else fan_in * mult * mult


def _mamba2_leaves(cfg: ModelConfig, k, n: int, dtype, norm01,
                   out_mult: float = 1.0) -> Params:
    """The leaves of `n` lone SSD mixers (`_ssd_block` names them), drawn as
    `_init_parallel_params` draws them, without a muP vector; `norm01(key,
    shape, fan_in)` is the caller's one-program-a-leaf draw and `out_mult`
    the scalar the mixer's output meets on its way into the stream."""
    h, H, P = cfg.hidden_size, cfg.ssd_heads, cfg.ssd_head_dim
    d_ssm, conv, taps = H * P, cfg.ssd_conv_dim, cfg.ssd_conv_kernel

    def spread(k, shape, out_dtype=dtype):
        return (1.0 + 0.2 * jax.random.normal(k, shape, jnp.float32)
                ).astype(out_dtype)

    ks = jax.random.split(k, 8)
    step = jnp.exp(jax.random.uniform(
        ks[5], (n, H), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
    return {
        "w_in": norm01(ks[0], (n, h, d_ssm + conv + H), h),
        "conv_w": norm01(ks[1], (n, taps, conv), taps),
        "conv_b": (0.1 * jax.random.normal(ks[2], (n, conv), jnp.float32)
                   ).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(
            ks[3], (n, H), jnp.float32, 1.0, 16.0)),
        "D": spread(ks[4], (n, H), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(step)),
        "ln_ssd": spread(ks[6], (n, d_ssm)),
        "w_out": norm01(ks[7], (n, d_ssm, h), _scaled(d_ssm, out_mult)),
    }


def _init_lead_tree_params(cfg: ModelConfig, key: jax.Array, dtype) -> Params:
    """Random weights of a `deepseek_v3`-style tree: `first_k_dense` dense
    layers stacked under "dense_layers", the routed ones (router + selection
    bias, experts, shared branch) under "layers", every layer with the SAME
    attention block: latent (`cfg.is_latent`: Kanana-2) or grouped-query
    (K-EXAONE: wq / wk / wv / wo, and with `cfg.qk_norm` a norm weight of
    head_dim a layer for q and for k).  Expert leaves are the `num_experts`
    HELD; the router and its bias keep the router's full width.  The
    selection bias is N(0, 0.1^2), not zero: with b = 0 a program that
    weighs by sigma + b, or chooses by sigma, passes every check; the latent
    norm's and the q / k norms' weights are 1 + N(0, 0.2^2) for the same
    reason.  The latent model's random stream is what it was before the
    grouped-query block came to this tree.

    The conv layout (`cfg.conv_L_cache`: LFM2) keeps "dense_layers" and
    "layers" for the norms and the feed-forward leaves and stacks each
    KIND's mixer under `params["attn"][kind]` in layer order
    (`cfg.kind_leaves`): the grouped-query block above for the attention
    layers, and for a conv layer W_in [H, 3H] (chunks B | C | u), the taps
    [L, H] (tap L - 1 multiplies the row's own product; N(0, 1 / L), so the
    taps that read the tail weigh as much as the one that does not and a
    check on the logits sees a lost tail) and W_out [H, H].

    The linear-attention layout (`cfg.delta_heads`: Solar-Open2) is the conv
    layout's tree with a DELTA mixer (`_delta_attention_block` names the
    leaves; with W = heads x head size: wq / wk / wv [H, W], the three
    convolutions' taps side by side [L, 3W], the decay's and the output
    gate's low-rank pairs [H, head size] and [head size, W], A_log a head
    drawn log U(1, 16) and dt_bias a channel the inverse softplus of a step
    drawn log-uniform in [0.001, 0.1], so a channel's decay a row spreads
    over 0.9999 .. 0.2 and a decay taken per head, or a state rounded to
    bfloat16, moves the logits) and, where the config gates its attention
    elementwise, "wgate" [H, heads x head_dim] among the attention leaves.

    The mixer-then-feed-forward layout (`cfg.mixer_then_ffn`: Granite-4.0-H)
    is the conv layout's tree with a lone SSD mixer a MAMBA2 layer
    (`_mamba2_leaves`), a softmax router of the router's full width with NO
    selection bias, the HELD experts and the shared SwiGLU.  Its scalars
    matter to a check only under THE MULTIPLIERS' rule of
    `_init_parallel_params`: W_k is drawn at its fan-in deviation times 2
    head_dim ** -0.5 / `attention_multiplier` (scores of deviation 2 under
    the published scale: at deviation 1 a softmax over the check's 1,536
    keys is still nearly their mean, the one attention layer in ten adds
    0.04 a value to the stream and a rotation read 0.021-0.025 against a
    served error of 0.018-0.025: my chip run 2, PR 63), the out-projections
    and the down matrices divided by `residual_multiplier`.  NOT the embedding: its
    head is TIED, and a row drawn at 1 / `embedding_multiplier` a value
    (times 12: unit variance, as Falcon-H1's untied tree draws it) makes the
    last token's own logit, 12 |E_t|^2, fourteen deviations of the other
    tokens' at 4,096 wide: greedy decoding then echoes its input for ever
    (every lane of the benchmark's cell repeated the prompt's last byte, the
    detokenizer held the invalid UTF-8 back and `tpot_p50_ms` read 0.0: my
    chip run 1b, PR 63).  At the fan-in deviation a row times 12 weighs 0.19
    a value beside sublayers of order 1: enough that a dropped multiplier
    moves the logits past the check's tolerance, and the echo is 2.7
    deviations, under the largest of 50,176 draws.  The logits come out at
    1 / 16 and the comparison is relative."""
    h, hq = cfg.hidden_size, cfg.num_heads
    r_mult = cfg.residual_multiplier

    @partial(jax.jit, static_argnums=(1, 2))
    def norm01(k, shape, fan_in):
        # one program a leaf: the draw, the scale and the cast fuse, so no
        # float32 copy of a 1G-element leaf is ever held (init_params' eager
        # form holds two, which is what caps the other configurations' depth)
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in**-0.5)).astype(dtype)

    def spread(k, shape):
        return (1.0 + 0.2 * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    def norms(n):
        if cfg.norm_position == "post":
            # not ones: a unit weight on a sublayer's OUTPUT would let a norm
            # moved ahead of the sublayer, or swapped with its neighbour's,
            # pass a check
            ka, km = jax.random.split(jax.random.fold_in(key, n + 11))
            return {"ln_attn": spread(ka, (n, h)),
                    "ln_mlp": spread(km, (n, h))}
        return {"ln_attn": jnp.ones((n, h), dtype),
                "ln_mlp": jnp.ones((n, h), dtype)}

    def latent_attention(k, n):
        r = cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        ks = jax.random.split(k, 5)
        return {
            **norms(n),
            # not ones: over 512 lanes of unit-variance c the RMS is already
            # 1 +- 3%, so with a unit weight a program that skips this norm
            # would pass every check
            "ln_kv": spread(ks[4], (n, r)),
            "wq": norm01(ks[0], (n, h, hq, dn + dr), h),
            "wkva": norm01(ks[1], (n, h, r + dr), h),
            # per head [k_nope | v]; the latent axis next to last, where
            # every stacked matrix has its contracted axis
            "wkvb": norm01(ks[2], (n, hq, r, dn + dv), r),
            "wo": norm01(ks[3], (n, hq, dv, h), hq * dv),
        }

    def gqa_attention(k, n, with_norms=True):
        hkv, d = cfg.num_kv_heads, cfg.head_dim
        ks = jax.random.split(k, 6)
        k_mult = (cfg.attention_multiplier * d**0.5 / 2.0
                  if cfg.attention_multiplier else 1.0)
        out = {
            **(norms(n) if with_norms else {}),
            "wq": norm01(ks[0], (n, h, hq, d), h),
            "wk": norm01(ks[1], (n, h, hkv, d), _scaled(h, k_mult)),
            "wv": norm01(ks[2], (n, h, hkv, d), h),
            "wo": norm01(ks[3], (n, hq, d, h), _scaled(hq * d, r_mult)),
        }
        if cfg.qk_norm:
            # (a weight over the whole projection where the norm is)
            whole = cfg.qk_norm_whole
            out["ln_q"] = spread(ks[4], (n, hq * d if whole else d))
            out["ln_k"] = spread(ks[5], (n, hkv * d if whole else d))
        if cfg.attention_gate == "elementwise":
            out["wgate"] = norm01(jax.random.fold_in(k, 6), (n, h, hq * d), h)
        return out

    def delta_mixer(k, n):
        H, D, taps = cfg.delta_heads, cfg.delta_head_dim, cfg.delta_conv_kernel
        W = H * D
        ks = jax.random.split(k, 13)
        step = jnp.exp(jax.random.uniform(
            ks[11], (n, W), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
        return {
            "wq": norm01(ks[0], (n, h, W), h),
            "wk": norm01(ks[1], (n, h, W), h),
            "wv": norm01(ks[2], (n, h, W), h),
            "conv_w": norm01(ks[3], (n, taps, 3 * W), taps),
            "wf1": norm01(ks[4], (n, h, D), h),
            "wf2": norm01(ks[5], (n, D, W), D),
            "wg1": norm01(ks[6], (n, h, D), h),
            "wg2": norm01(ks[7], (n, D, W), D),
            "wbeta": norm01(ks[8], (n, h, H), h),
            "ln_o": spread(ks[9], (n, D)),
            "A_log": jnp.log(jax.random.uniform(
                ks[10], (n, H), jnp.float32, 1.0, 16.0)),
            "dt_bias": jnp.log(jnp.expm1(step)),
            "w_out": norm01(ks[12], (n, W, h), W),
        }

    def gdn_mixer(k, n):
        """Gated DeltaNet's leaves (`cfg.delta_gate` "head"): A_log a head
        is log U(0, 16) and dt_bias a head the inverse softplus of a step
        drawn log-uniform in [0.001, 0.1], the layer's own initialiser, so
        some heads forget in a few rows and others carry the whole prefix (a
        state lost at a launch boundary moves the logits); W_a is drawn at
        `GDN_A_GAIN` times its fan-in deviation, so that the bias and not the
        projection of a post-normed stream (whose size grows with depth)
        sets a head's rate.  W_v is drawn at
        `GDN_V_GAIN` times its fan-in deviation: o = S^T q then lies within
        two orders of sqrt(eps), where the head norm behind it is NOT blind
        to o's scale, so a dropped q scale moves the logits."""
        H, D, Dv = cfg.delta_heads, cfg.delta_head_dim, cfg.delta_v_dim
        taps = cfg.delta_conv_kernel
        ks = jax.random.split(k, 12)
        step = jnp.exp(jax.random.uniform(
            ks[9], (n, H), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
        return {
            "wq": norm01(ks[0], (n, h, H * D), h),
            "wk": norm01(ks[1], (n, h, H * D), h),
            "wv": norm01(ks[2], (n, h, H * Dv), _scaled(h, 1.0 / GDN_V_GAIN)),
            "conv_w": norm01(ks[3], (n, taps, H * (2 * D + Dv)), taps),
            "wa": norm01(ks[4], (n, h, H), _scaled(h, 1.0 / GDN_A_GAIN)),
            "wbeta": norm01(ks[5], (n, h, H), h),
            "wgo": norm01(ks[6], (n, h, H * Dv), h),
            "ln_o": spread(ks[7], (n, Dv)),
            "A_log": jnp.log(jax.random.uniform(
                ks[8], (n, H), jnp.float32, 1e-4, 16.0)),
            "dt_bias": jnp.log(jnp.expm1(step)),
            "w_out": norm01(ks[10], (n, H * Dv, h), H * Dv),
        }

    def conv_mixer(k, n):
        taps = cfg.conv_L_cache
        ks = jax.random.split(k, 3)
        return {"w_in": norm01(ks[0], (n, h, 3 * h), h),
                "conv_w": norm01(ks[1], (n, taps, h), taps),
                "w_out": norm01(ks[2], (n, h, h), h)}

    attention = latent_attention if cfg.is_latent else gqa_attention
    # the conv layout: a mixer a KIND, and the two stacks keep the norms
    def mamba2_mixer(k, n):
        return _mamba2_leaves(cfg, k, n, dtype, norm01, r_mult)

    mixers = {CONV: conv_mixer, MAMBA2: mamba2_mixer,
              DELTA: gdn_mixer if cfg.delta_gate == "head" else delta_mixer,
              GLOBAL: partial(gqa_attention, with_norms=False)}
    kinded = (CONV in cfg.layer_types or DELTA in cfg.layer_types
              or cfg.mixer_then_ffn)
    if kinded:
        def attention(k, n):
            return norms(n)

    def mlp(k, n, f, names=("wg", "wu", "wd")):
        ks = jax.random.split(k, 3)
        return {names[0]: norm01(ks[0], (n, h, f), h),
                names[1]: norm01(ks[1], (n, h, f), h),
                names[2]: norm01(ks[2], (n, f, h), _scaled(f, r_mult))}

    keys = jax.random.split(key, 10)
    n_dense = cfg.first_k_dense
    n = cfg.num_layers - n_dense
    layers = attention(keys[1], n)
    if cfg.is_moe:
        E, f = cfg.num_experts, cfg.intermediate_size
        routed = cfg.num_router_experts
        layers["router"] = norm01(keys[2], (n, h, routed), h)
        if cfg.moe_scoring == "sigmoid":
            layers["router_bias"] = 0.1 * jax.random.normal(
                keys[3], (n, routed), jnp.float32)
        layers["wg"] = norm01(keys[4], (n, E, h, f), h)
        layers["wu"] = norm01(keys[5], (n, E, h, f), h)
        layers["wd"] = norm01(keys[6], (n, E, f, h), _scaled(f, r_mult))
        if cfg.shared_intermediate_size:
            layers.update(mlp(keys[7], n, cfg.shared_intermediate_size,
                              ("ws_g", "ws_u", "ws_d")))
    else:
        layers.update(mlp(keys[4], n, cfg.intermediate_size))
    params: Params = {
        "embed": norm01(keys[0], (cfg.vocab_size, h), h),
        "final_norm": jnp.ones((h,), dtype),
        "layers": layers,
    }
    if kinded:
        params["attn"] = {
            kind: mixers[kind](jax.random.fold_in(keys[1], i),
                               cfg.layers_of(kind))
            for i, kind in enumerate(cfg.kinds)}
    if n_dense:
        kd = jax.random.split(keys[8], 2)
        params["dense_layers"] = {
            **attention(kd[0], n_dense),
            **mlp(kd[1], n_dense, cfg.dense_intermediate_size)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm01(keys[9], (h, cfg.vocab_size), h)
    return params


def _init_lone_params(cfg: ModelConfig, key: jax.Array, dtype) -> Params:
    """Random weights of the one-sublayer layout (`nemotron_h`): "layers"
    holds each layer's ONE norm, "ln" [L, H]; every other leaf is stacked per
    KIND of layer in layer order, the mixers' under `params["attn"][kind]`
    (a lone SSD mixer's as `_init_parallel_params` names and draws them,
    without multipliers; attention's wq / wk / wv / wo) and the routed
    feed-forward's under `params["ffn"][kind]`: the router at its full width
    and its selection bias N(0, 0.1^2), the HELD experts' "wu" and "wd", both
    [n, E, f, H] (an ungated expert has no gate matrix, and its up matrix is
    stored out x in: models/ffn.ACTIVATIONS) and the shared expert's "ws_u"
    [n, H, fs] / "ws_d".  A homogeneous [L, ...] stack would give every
    layer experts.
    The down matrices of a squared-ReLU block are drawn at 1 / sqrt(1.5 f):
    relu(u)^2 of a unit normal u has second moment 3 / 2, so the block's
    output is of unit variance as every other preset's is."""
    h, hq, hkv, d = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                     cfg.head_dim)

    @partial(jax.jit, static_argnums=(1, 2))
    def norm01(k, shape, fan_in):
        # one program a leaf: no float32 copy of a 2G-element leaf is held
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in**-0.5)).astype(dtype)

    def mamba2_mixer(k, n):
        return _mamba2_leaves(cfg, k, n, dtype, norm01)

    def gqa_attention(k, n):
        ks = jax.random.split(k, 4)
        return {"wq": norm01(ks[0], (n, h, hq, d), h),
                "wk": norm01(ks[1], (n, h, hkv, d), h),
                "wv": norm01(ks[2], (n, h, hkv, d), h),
                "wo": norm01(ks[3], (n, hq, d, h), hq * d)}

    def routed(k, n):
        E, f, fs = (cfg.num_experts, cfg.intermediate_size,
                    cfg.shared_intermediate_size)
        width = cfg.num_router_experts
        ks = jax.random.split(k, 6)
        out = {
            "router": norm01(ks[0], (n, h, width), h),
            "router_bias": 0.1 * jax.random.normal(
                ks[1], (n, width), jnp.float32),
            # (an ungated expert's up matrix out x in: models/ffn.ACTIVATIONS)
            "wu": norm01(ks[2], (n, E, f, h), h),
            "wd": norm01(ks[3], (n, E, f, h), 1.5 * f),
        }
        if fs:
            out["ws_u"] = norm01(ks[4], (n, h, fs), h)
            out["ws_d"] = norm01(ks[5], (n, fs, h), 1.5 * fs)
        return out

    keys = jax.random.split(key, 5)
    mixers = {MAMBA2: mamba2_mixer, GLOBAL: gqa_attention}
    params: Params = {
        "embed": norm01(keys[0], (cfg.vocab_size, h), h),
        "final_norm": jnp.ones((h,), dtype),
        "layers": {"ln": jnp.ones((cfg.num_layers, h), dtype)},
        "attn": {kind: mixers[kind](jax.random.fold_in(keys[1], i),
                                    cfg.layers_of(kind))
                 for i, kind in enumerate(cfg.kinds) if kind in mixers},
        "ffn": {MOE: routed(keys[2], cfg.layers_of(MOE))},
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm01(keys[3], (h, cfg.vocab_size), h)
    return params


def _init_kind_params(cfg: ModelConfig, key: jax.Array, dtype) -> Params:
    """Random weights of a latent decoder whose attention is PER KIND of
    layer (`cfg.by_kind`): "attn" holds, per kind, the attention leaves of
    all that kind's layers stacked in layer order (dense and routed alike);
    "dense_layers" and "layers" hold the norms and the FFN leaves.  Expert
    leaves are the `num_experts` HELD; the router and its selection bias keep
    the router's full width.  A matrix that reads a rescaled latent counts
    the rescale in its fan-in, so queries, keys and values come out at unit
    scale as everywhere else; norm weights and biases are spread (not 1 / 0)
    so that a program that skips one fails the check, as
    `_init_lead_tree_params` says."""
    h = cfg.hidden_size

    @partial(jax.jit, static_argnums=(1, 2))
    def norm01(k, shape, fan_in):
        # one program a leaf: no float32 copy of a 0.75G-element leaf is held
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in**-0.5)).astype(dtype)

    def spread(k, shape, mean=1.0, sd=0.2):
        return (mean + sd * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    def gain(rank):
        return h / rank if cfg.latent_rescale else 1.0

    def attention(k, kind, n):
        g = cfg.geometry_of(kind)
        hq, r, rq = g.num_heads, g.kv_lora_rank, g.q_lora_rank
        dn, dr, dv = g.qk_nope_head_dim, g.qk_rope_head_dim, g.v_head_dim
        ks = jax.random.split(k, 12)
        out = {
            "ln_kv": spread(ks[0], (n, r)),
            "wkva": norm01(ks[1], (n, h, r + dr), h),
            "wkvb": norm01(ks[2], (n, hq, r, dn + dv), r * gain(r)),
            "wo": norm01(ks[3], (n, hq, dv, h), hq * dv),
        }
        if rq:
            out["wqa"] = norm01(ks[4], (n, h, rq), h)
            out["ln_q"] = spread(ks[5], (n, rq))
            out["wqb"] = norm01(ks[6], (n, rq, hq, dn + dr), rq * gain(rq))
        else:
            out["wq"] = norm01(ks[4], (n, h, hq, dn + dr), h)
        if cfg.attention_gate:
            out["wgate"] = norm01(ks[7], (n, h, hq), h)
        if cfg.has_indexer(kind):
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            src, fan = (rq, rq * gain(rq)) if rq else (h, h)
            out["wiq"] = norm01(ks[8], (n, src, hi, di), fan)
            out["wik"] = norm01(ks[9], (n, h, di), h)
            out["ln_ik"] = spread(ks[10], (n, di))
            out["ln_ik_b"] = spread(jax.random.fold_in(ks[10], 1), (n, di),
                                    0.0, 0.1)
            out["wiw"] = norm01(ks[11], (n, h, hi), h)
        return out

    def mlp(k, n, f, names=("wg", "wu", "wd")):
        ks = jax.random.split(k, 3)
        return {names[0]: norm01(ks[0], (n, h, f), h),
                names[1]: norm01(ks[1], (n, h, f), h),
                names[2]: norm01(ks[2], (n, f, h), f)}

    def norms(n):
        return {"ln_attn": jnp.ones((n, h), dtype),
                "ln_mlp": jnp.ones((n, h), dtype)}

    def stream_maps(k, n):
        """The residual stream's mappings, both sites of `n` layers (the
        leaves `_hc_in` names; {} where the stream is one row).  NOT the
        paper's initial values (alpha 0.01, H_res near the identity): there
        the dynamic term sits under any bfloat16 tolerance and Sinkhorn's
        input is nearly a permutation, so a program without either would
        pass every check.  alpha = 1; Phi N(0, 1 / nC), so its product with
        the normed stream is of unit scale; the biases N(0, 1), H_res's plus
        2 I; the stream norm's weight spread like every norm a check must
        see."""
        m = cfg.hc_mult
        if m == 1:
            return {}
        out = {}
        for s, site in enumerate(HC_SITES):
            ks = jax.random.split(jax.random.fold_in(k, s), 3)
            bias = jax.random.normal(ks[1], (n, 2 * m + m * m), jnp.float32)
            out.update({
                f"hc_{site}_phi": norm01(ks[0], (n, m * h, 2 * m + m * m),
                                         m * h),
                f"hc_{site}_bias": bias.at[:, 2 * m:].add(
                    2.0 * jnp.eye(m).reshape(-1)),
                f"hc_{site}_alpha": jnp.ones((n, 3), jnp.float32),
                f"hc_{site}_norm": spread(ks[2], (n, m * h)),
            })
        return out

    keys = jax.random.split(key, 12)
    n_dense = cfg.first_k_dense
    n = cfg.num_layers - n_dense
    layers = {**norms(n), **stream_maps(keys[10], n)}
    if cfg.is_moe:
        E, f = cfg.num_experts, cfg.intermediate_size
        layers["router"] = norm01(keys[2], (n, h, cfg.num_router_experts), h)
        if cfg.moe_scoring == "sigmoid":
            layers["router_bias"] = 0.1 * jax.random.normal(
                keys[3], (n, cfg.num_router_experts), jnp.float32)
        layers["wg"] = norm01(keys[4], (n, E, h, f), h)
        layers["wu"] = norm01(keys[5], (n, E, h, f), h)
        layers["wd"] = norm01(keys[6], (n, E, f, h), f)
        if cfg.shared_intermediate_size:
            layers.update(mlp(keys[7], n, cfg.shared_intermediate_size,
                              ("ws_g", "ws_u", "ws_d")))
    else:
        layers.update(mlp(keys[4], n, cfg.intermediate_size))
    params: Params = {
        "embed": norm01(keys[0], (cfg.vocab_size, h), h),
        "final_norm": jnp.ones((h,), dtype),
        "layers": layers,
        "attn": {kind: attention(jax.random.fold_in(keys[1], i), kind,
                                 cfg.layers_of(kind))
                 for i, kind in enumerate(cfg.kinds)},
    }
    if n_dense:
        params["dense_layers"] = {
            **norms(n_dense), **stream_maps(keys[11], n_dense),
            **mlp(keys[8], n_dense, cfg.dense_intermediate_size)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm01(keys[9], (h, cfg.vocab_size), h)
    return params
