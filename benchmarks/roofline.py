"""Peaks of the chips the benchmark runs on, and the operations and bytes an
algorithm NEEDS for a call, from its shapes.  A roofline share is the least
time the chip could take (the larger of flops / peak flops and bytes / peak
bandwidth) over the kernel's measured device time.

The program keeps a table of its own (`runtime/planner.py` DEVICE_KINDS) for
planning; this is the benchmark's copy, which a PR that claims a gain cannot
change.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

# Keyed by the exact `device_kind` JAX reports.  Source: Google Cloud
# documentation, "TPU v5e" system architecture: 197 TFLOP/s bf16 and 394
# TOP/s int8 per chip, 16 GB HBM2e at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """An unknown kind is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str) -> Tuple[float, str]:
    """(share in %, which bound) of a call that took `seconds` on the device."""
    pk = peaks(device_kind)
    t_flops = flops / pk["flops_bf16"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "bandwidth"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound


def _pages(n: int, page_size: int) -> int:
    return -(-n // page_size)


def paged_decode(seq_lens: Iterable[int], num_heads: int, num_kv_heads: int,
                 head_dim: int, page_size: int,
                 dtype_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of ONE paged-decode attention call (one layer): every
    lane reads the pages of its own context once (K and V), plus q in and
    out.  QK^T and PV are 2 * len * Hq * D flops each."""
    flops = nbytes = 0.0
    for n in seq_lens:
        n = int(n)
        if n <= 0:
            continue
        flops += 4.0 * n * num_heads * head_dim
        nbytes += (2.0 * _pages(n, page_size) * page_size * num_kv_heads
                   * head_dim * dtype_bytes)
        nbytes += 2.0 * num_heads * head_dim * dtype_bytes
    return flops, nbytes


def flash_prefill(chunk_len: int, start: int, num_heads: int,
                  num_kv_heads: int, head_dim: int,
                  dtype_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of ONE flash-prefill call (one layer): `chunk_len`
    queries at positions start.., each attending causally to start + i + 1
    keys.  K and V of the whole context are read once, q in and out."""
    s, c = int(chunk_len), int(start)
    pairs = s * c + s * (s + 1) / 2.0
    flops = 4.0 * pairs * num_heads * head_dim
    nbytes = (2.0 * (c + s) * num_kv_heads * head_dim * dtype_bytes
              + 2.0 * s * num_heads * head_dim * dtype_bytes)
    return flops, nbytes


def layer_weight_bytes(hf: Dict, dtype_bytes: int = 2) -> float:
    """One decoder layer's weights as the dense-dispatch program reads them:
    every expert of a routed layer is read for every step."""
    h, f = hf["hidden_size"], hf["intermediate_size"]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    d = hf.get("head_dim", h // hq)
    attn = h * hq * d * 2 + h * hkv * d * 2
    experts = hf.get("num_local_experts", 0) or 0
    mlp = 3 * h * f * max(experts, 1) + h * experts
    return float((attn + mlp + 2 * h) * dtype_bytes)


def decode_step(hf: Dict, seq_lens: Iterable[int], page_size: int,
                dtype_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of one whole decode step of the model in `hf` (the
    published config.json keys) over lanes with the given contexts: all
    layer weights and the logits head once, one embedding row and the KV of
    its context per lane.  A routed layer computes top-k experts' flops (what
    the algorithm needs) but reads all of them only if the batch routes to
    all; with 16 lanes and top-2 of 8 that is the common case, so bytes count
    every expert."""
    lens = [int(n) for n in seq_lens if int(n) > 0]
    b = len(lens)
    h, f, v = hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    d = hf.get("head_dim", h // hq)
    layers = hf["num_hidden_layers"]
    experts = hf.get("num_local_experts", 0) or 0
    k = hf.get("num_experts_per_tok", 2) if experts else 1
    a_flops, a_bytes = paged_decode(lens, hq, hkv, d, page_size, dtype_bytes)
    per_tok = 2.0 * (h * hq * d * 2 + h * hkv * d * 2 + 3 * h * f * k
                     + h * experts)
    flops = layers * (per_tok * b + a_flops) + 2.0 * h * v * b
    nbytes = (layers * (layer_weight_bytes(hf, dtype_bytes) + a_bytes)
              + h * v * dtype_bytes + b * h * dtype_bytes)
    return flops, nbytes
