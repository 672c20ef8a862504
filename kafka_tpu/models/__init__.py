"""Model family: configs, the functional decoder (Llama / Mixtral style, and
layer patterns of windowed and global attention), checkpoint loading,
tokenizers."""

from .config import CONFIGS, ModelConfig, config_from_hf_json, get_config
from .llama import KVCache, forward, init_kv_cache, init_params
from .loader import convert_hf_state_dict, load_checkpoint, resolve_checkpoint_dir
from .quant import QTensor, dequantize, quantize_params
from .tokenizer import (
    BaseTokenizer,
    ByteTokenizer,
    HFTokenizer,
    load_tokenizer,
    parse_tool_call_text,
)

__all__ = [
    "CONFIGS",
    "ModelConfig",
    "config_from_hf_json",
    "get_config",
    "KVCache",
    "forward",
    "init_kv_cache",
    "init_params",
    "convert_hf_state_dict",
    "load_checkpoint",
    "resolve_checkpoint_dir",
    "QTensor",
    "dequantize",
    "quantize_params",
    "BaseTokenizer",
    "ByteTokenizer",
    "HFTokenizer",
    "load_tokenizer",
    "parse_tool_call_text",
]
