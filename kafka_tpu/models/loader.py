"""Checkpoint loading: HuggingFace Llama weights -> layer-stacked JAX pytree.

Sources supported:
  * a directory of HF `*.safetensors` shards (+ config.json) — the serving
    path; tensors are memory-mapped and never pass through torch;
  * an in-memory torch/HF state dict — used by the numerics tests, which
    build a tiny random `transformers.LlamaForCausalLM` and check our logits
    against it.

Layout conversion: HF stores projection weights as [out, in] matrices per
layer; we transpose to [in, out] (einsum-natural, and the orientation that
shards over a ("tp",) mesh axis without relayout) and stack all layers on a
leading [L, ...] axis for `lax.scan` (see models/llama.py).
"""

from __future__ import annotations

import glob
import os
from typing import Any, Callable, Dict, Mapping, Optional

import jax.numpy as jnp
import numpy as np

from .config import GLOBAL, MAMBA2, MOE, ModelConfig, config_from_hf_json

Params = Dict[str, Any]


def _to_numpy(t: Any) -> np.ndarray:
    """Accept torch tensors or numpy arrays."""
    if isinstance(t, np.ndarray):
        return t
    # torch tensor (avoid importing torch unless needed)
    if hasattr(t, "detach"):
        t = t.detach()
        if t.dtype is not None and "bfloat16" in str(t.dtype):
            t = t.float()
        return t.cpu().numpy()
    return np.asarray(t)


def _getter(state: Mapping[str, Any],
            prefix: str = "model.") -> Callable[[str], np.ndarray]:
    """`get(name)`: a weight by its HF name, with or without the family's
    prefix (`model.`; `nemotron_h`'s `backbone.`), as numpy."""
    def get(name: str) -> np.ndarray:
        key = name if name in state else prefix + name
        if key not in state:
            raise KeyError(f"missing weight {name!r} (tried {key!r})")
        return _to_numpy(state[key])

    return get


def convert_hf_state_dict(
    state: Mapping[str, Any], cfg: ModelConfig, dtype: Optional[Any] = None
) -> Params:
    """Convert an HF Llama state dict to the layer-stacked pytree."""
    dtype = dtype or cfg.activation_dtype
    if cfg.is_latent:
        return _convert_latent_state_dict(state, cfg, dtype)
    if cfg.lone_layers:
        return _convert_lone_state_dict(state, cfg, dtype)
    if cfg.mixer_then_ffn:
        raise NotImplementedError(
            "no checkpoint converter for the mixer-then-feed-forward layout "
            "(`granitemoehybrid`): its tree (Mamba-2 mixers and attention "
            "stacked per kind under `attn`, a softmax router, a held share "
            "of the experts and a shared expert under `layers`) is "
            "models/init_params._init_lead_tree_params', served on seeded "
            "weights")
    if cfg.delta_gate == "head" and cfg.delta_heads:
        raise NotImplementedError(
            "no checkpoint converter for the Gated DeltaNet layout "
            "(`olmo_hybrid`): its tree (the linear mixers and the post-normed "
            "attention stacked per kind under `attn`, dense feed-forwards "
            "under `layers`) is models/init_params._init_lead_tree_params', "
            "served on seeded weights until the published files are here")
    if cfg.lead_tree or cfg.qk_norm:
        raise NotImplementedError(
            "no checkpoint converter for a grouped-query model with a dense "
            "lead, shared experts or QK-norm (`exaone_moe`): its tree is "
            "models/init_params._init_lead_tree_params', served on seeded weights")
    h, d = cfg.hidden_size, cfg.head_dim
    hq, hkv, L = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers

    get = _getter(state)

    def stack(fmt: str, reshape: Callable[[np.ndarray], np.ndarray]) -> jnp.ndarray:
        return jnp.asarray(
            np.stack([reshape(get(fmt.format(i=i))) for i in range(L)]), dtype
        )

    layers = {
        "ln_attn": stack("layers.{i}.input_layernorm.weight", lambda w: w),
        "ln_mlp": stack("layers.{i}.post_attention_layernorm.weight", lambda w: w),
        "wq": stack(
            "layers.{i}.self_attn.q_proj.weight", lambda w: w.T.reshape(h, hq, d)
        ),
        "wk": stack(
            "layers.{i}.self_attn.k_proj.weight", lambda w: w.T.reshape(h, hkv, d)
        ),
        "wv": stack(
            "layers.{i}.self_attn.v_proj.weight", lambda w: w.T.reshape(h, hkv, d)
        ),
        "wo": stack(
            "layers.{i}.self_attn.o_proj.weight", lambda w: w.T.reshape(hq, d, h)
        ),
    }
    if cfg.is_moe:
        # HF Mixtral layout: block_sparse_moe.gate [E, H] router;
        # experts.{e}.w1/w3/w2 = gate/up/down [F, H] / [F, H] / [H, F].
        # Stacked here to [L, E, H, F] (w1/w3 transposed) and [L, E, F, H].
        E = cfg.num_experts

        def stack_experts(wname: str) -> jnp.ndarray:
            return jnp.asarray(np.stack([
                np.stack([
                    get(f"layers.{i}.block_sparse_moe.experts.{e}."
                        f"{wname}.weight").T
                    for e in range(E)
                ]) for i in range(L)
            ]), dtype)

        layers["router"] = stack(
            "layers.{i}.block_sparse_moe.gate.weight", lambda w: w.T
        )
        layers["wg"] = stack_experts("w1")
        layers["wu"] = stack_experts("w3")
        layers["wd"] = stack_experts("w2")
    else:
        layers["wg"] = stack("layers.{i}.mlp.gate_proj.weight", lambda w: w.T)
        layers["wu"] = stack("layers.{i}.mlp.up_proj.weight", lambda w: w.T)
        layers["wd"] = stack("layers.{i}.mlp.down_proj.weight", lambda w: w.T)
    params: Params = {
        "embed": jnp.asarray(get("embed_tokens.weight"), dtype),
        "final_norm": jnp.asarray(get("norm.weight"), dtype),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        head = state.get("lm_head.weight")
        if head is None:
            raise KeyError("config says untied embeddings but lm_head.weight missing")
        params["lm_head"] = jnp.asarray(_to_numpy(head).T, dtype)
    return params


def _convert_latent_state_dict(
    state: Mapping[str, Any], cfg: ModelConfig, dtype: Any
) -> Params:
    """HF `deepseek_v3` names -> the latent tree: leading dense layers
    stacked under "dense_layers", routed ones under "layers".  Without a
    query low-rank, models/init_params._init_lead_tree_params' tree (attention
    leaves in the two stacks).  With one kind of layer past that block
    (`cfg.by_kind`: a query low-rank `q_a_proj` / `q_a_layernorm` /
    `q_b_proj`, a widened residual stream's `hc_*` leaves a site),
    _init_kind_params' tree: the attention leaves of ALL layers in layer
    order under "attn".  A multi-token-prediction module's keys (`mtp.*`,
    layers past `num_hidden_layers`) are never asked for, which is how they
    are dropped (`cfg.nextn_predict_layers`: recorded, not built).  Rotary
    columns stay interleaved as published; `forward` de-interleaves them
    (`cfg.rope_interleave`)."""
    h, hq, r = cfg.hidden_size, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    if cfg.by_kind and (cfg.layer_types or cfg.index_topk
                        or cfg.attention_gate or cfg.latent_rescale
                        or cfg.windowed_latent):
        raise NotImplementedError(
            "no checkpoint converter for a latent model with kinds of "
            "layer, an indexer, a gate or a rescale (`dots3_note`): its "
            "tree is models/init_params._init_kind_params', served on seeded "
            "weights")

    get = _getter(state)

    t = lambda w: w.T  # noqa: E731  [out, in] -> [in, out]
    norms = {
        "ln_attn": ("input_layernorm.weight", None),
        "ln_mlp": ("post_attention_layernorm.weight", None),
    }
    mixer = {
        "ln_kv": ("self_attn.kv_a_layernorm.weight", None),
        "wkva": ("self_attn.kv_a_proj_with_mqa.weight", t),
        "wkvb": ("self_attn.kv_b_proj.weight",
                 lambda w: w.reshape(hq, dn + dv, r).transpose(0, 2, 1)),
        "wo": ("self_attn.o_proj.weight",
               lambda w: w.T.reshape(hq, dv, h)),
    }
    if cfg.q_lora_rank:
        rq = cfg.q_lora_rank
        mixer.update({
            "wqa": ("self_attn.q_a_proj.weight", t),
            "ln_q": ("self_attn.q_a_layernorm.weight", None),
            "wqb": ("self_attn.q_b_proj.weight",
                    lambda w: w.T.reshape(rq, hq, dn + dr)),
        })
    else:
        mixer["wq"] = ("self_attn.q_proj.weight",
                       lambda w: w.T.reshape(h, hq, dn + dr))
    # a widened residual stream's leaves a site (published names ASSUMED: no
    # checkpoint of such a model is at hand; they follow the `hc_*` config
    # keys); Phi's three maps' rows pre | post | res; the float32 leaves apart
    stream, stream32 = {}, {}
    if cfg.hc_mult > 1:
        for site, hf_site in (("attn", "attn_hc"), ("mlp", "mlp_hc")):
            stream[f"hc_{site}_phi"] = (f"{hf_site}.hc_fn.weight", t)
            stream[f"hc_{site}_norm"] = (f"{hf_site}.hc_norm.weight", None)
            stream32[f"hc_{site}_bias"] = (f"{hf_site}.hc_base", None)
            stream32[f"hc_{site}_alpha"] = (f"{hf_site}.hc_scale", None)
    # (the lead tree keeps the mixer's leaves in the two stacks)
    attention = {**norms, **stream, **({} if cfg.by_kind else mixer)}

    def mlp(prefix: str, names=("wg", "wu", "wd")) -> dict:
        return {names[0]: (f"{prefix}.gate_proj.weight", t),
                names[1]: (f"{prefix}.up_proj.weight", t),
                names[2]: (f"{prefix}.down_proj.weight", t)}

    def stack(leaves: dict, ids, dt=dtype) -> dict:
        return {name: jnp.asarray(np.stack([
            (fn or (lambda w: w))(get(f"layers.{i}.{hf}")) for i in ids]), dt)
            for name, (hf, fn) in leaves.items()}

    n_dense = cfg.first_k_dense
    routed_ids = range(n_dense, cfg.num_layers)
    if cfg.is_moe:
        layers = stack({**attention, "router": ("mlp.gate.weight", t)},
                       routed_ids)
        layers.update(stack(
            {"router_bias": ("mlp.gate.e_score_correction_bias", None)},
            routed_ids, jnp.float32))
        for name, hf in (("wg", "gate_proj"), ("wu", "up_proj"),
                         ("wd", "down_proj")):
            layers[name] = jnp.asarray(np.stack([np.stack([
                get(f"layers.{i}.mlp.experts.{e}.{hf}.weight").T
                for e in range(cfg.num_experts)]) for i in routed_ids]), dtype)
        if cfg.shared_intermediate_size:
            layers.update(stack(
                mlp("mlp.shared_experts", ("ws_g", "ws_u", "ws_d")),
                routed_ids))
    else:
        layers = stack({**attention, **mlp("mlp")}, routed_ids)
    layers.update(stack(stream32, routed_ids, jnp.float32))
    params: Params = {
        "embed": jnp.asarray(get("embed_tokens.weight"), dtype),
        "final_norm": jnp.asarray(get("norm.weight"), dtype),
        "layers": layers,
    }
    if cfg.by_kind:
        (kind,) = cfg.kinds
        params["attn"] = {kind: stack(mixer, range(cfg.num_layers))}
    if n_dense:
        params["dense_layers"] = {
            **stack({**attention, **mlp("mlp")}, range(n_dense)),
            **stack(stream32, range(n_dense), jnp.float32)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(
            _to_numpy(state["lm_head.weight"]).T, dtype)
    return params


def _convert_lone_state_dict(
    state: Mapping[str, Any], cfg: ModelConfig, dtype: Any
) -> Params:
    """HF `nemotron_h` names -> the one-sublayer tree
    (models/init_params._init_lone_params): `backbone.layers.N.norm` under
    "layers", `backbone.layers.N.mixer.*` stacked per KIND of layer in layer
    order, a Mamba-2 mixer's and attention's under "attn", a routed
    feed-forward's under "ffn"; of the published experts the
    `cfg.num_experts` from `cfg.expert_offset` are read (a held share asks
    for no other).  The convolution's taps [C, 1, taps] become [taps, C]:
    the last tap multiplies the row's own value in both."""
    h, d = cfg.hidden_size, cfg.head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    get = _getter(state, "backbone.")
    t = lambda w: w.T  # noqa: E731  [out, in] -> [in, out]
    f32 = jnp.float32
    leaves = {
        MAMBA2: {
            "w_in": ("in_proj.weight", t, dtype),
            "conv_w": ("conv1d.weight", lambda w: w[:, 0, :].T, dtype),
            "conv_b": ("conv1d.bias", None, dtype),
            "A_log": ("A_log", None, f32),
            "D": ("D", None, f32),
            "dt_bias": ("dt_bias", None, f32),
            "ln_ssd": ("norm.weight", None, dtype),
            "w_out": ("out_proj.weight", t, dtype),
        },
        GLOBAL: {
            "wq": ("q_proj.weight", lambda w: w.T.reshape(h, hq, d), dtype),
            "wk": ("k_proj.weight", lambda w: w.T.reshape(h, hkv, d), dtype),
            "wv": ("v_proj.weight", lambda w: w.T.reshape(h, hkv, d), dtype),
            "wo": ("o_proj.weight", lambda w: w.T.reshape(hq, d, h), dtype),
        },
        MOE: {
            "router": ("gate.weight", t, dtype),
            "router_bias": ("gate.e_score_correction_bias", None, f32),
            "ws_u": ("shared_experts.up_proj.weight", t, dtype),
            "ws_d": ("shared_experts.down_proj.weight", t, dtype),
        },
    }
    ids = {kind: [i for i in range(cfg.num_layers) if cfg.kind_of(i) == kind]
           for kind in cfg.kinds}

    def stack(kind: str) -> dict:
        return {name: jnp.asarray(np.stack([
            (fn or (lambda w: w))(get(f"layers.{i}.mixer.{hf}"))
            for i in ids[kind]]), dt)
            for name, (hf, fn, dt) in leaves[kind].items()}

    routed = stack(MOE)
    held = range(cfg.expert_offset, cfg.expert_offset + cfg.num_experts)
    # (both [f, H]: the up matrix as published, the down matrix transposed)
    for name, hf, fn in (("wu", "up_proj", lambda w: w),
                         ("wd", "down_proj", t)):
        routed[name] = jnp.asarray(np.stack([np.stack([
            fn(get(f"layers.{i}.mixer.experts.{e}.{hf}.weight"))
            for e in held]) for i in ids[MOE]]), dtype)
    return {
        "embed": jnp.asarray(get("embeddings.weight"), dtype),
        "final_norm": jnp.asarray(get("norm_f.weight"), dtype),
        "layers": {"ln": jnp.asarray(np.stack([
            get(f"layers.{i}.norm.weight")
            for i in range(cfg.num_layers)]), dtype)},
        "attn": {kind: stack(kind) for kind in cfg.kinds if kind != MOE},
        "ffn": {MOE: routed},
        "lm_head": jnp.asarray(_to_numpy(state["lm_head.weight"]).T, dtype),
    }


def load_safetensors_dir(path: str) -> Dict[str, np.ndarray]:
    """Load all tensors from a directory of .safetensors shards (numpy)."""
    from safetensors import safe_open

    tensors: Dict[str, np.ndarray] = {}
    files = sorted(glob.glob(os.path.join(path, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {path}")
    for f in files:
        with safe_open(f, framework="np") as reader:
            for name in reader.keys():
                tensors[name] = reader.get_tensor(name)
    return tensors


def load_checkpoint(path: str, cfg: Optional[ModelConfig] = None) -> tuple:
    """Load (cfg, params) from an HF checkpoint directory."""
    if cfg is None:
        cfg = config_from_hf_json(os.path.join(path, "config.json"))
    state = load_safetensors_dir(path)
    return cfg, convert_hf_state_dict(state, cfg)


def resolve_checkpoint_dir(model_name: str) -> Optional[str]:
    """Find a local checkpoint dir for a model name, if one exists.

    Search order: $KAFKA_TPU_CKPT_DIR/<name>, ./checkpoints/<name>,
    the HF cache. Returns None when the model must run random-init
    (tests/benchmarks without downloaded weights — this environment has no
    network egress)."""
    candidates = []
    env_dir = os.environ.get("KAFKA_TPU_CKPT_DIR")
    if env_dir:
        candidates.append(os.path.join(env_dir, model_name))
    candidates.append(os.path.join("checkpoints", model_name))
    hf_cache = os.path.expanduser(
        os.environ.get("HF_HOME", "~/.cache/huggingface")
    )
    candidates.extend(
        glob.glob(
            os.path.join(
                hf_cache, "hub", f"models--*{model_name}*", "snapshots", "*"
            )
        )
    )
    for c in candidates:
        if os.path.isdir(c) and glob.glob(os.path.join(c, "*.safetensors")):
            return c
    return None
