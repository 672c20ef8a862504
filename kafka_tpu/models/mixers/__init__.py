"""A layer's mixer: one signature, one table.

    mix(x, lp, ctx, kc, vc, layer, kind) -> (out, kc, vc)

x [B, S, H] is the layer's normed input, `lp` its leaves, `kc` / `vc` the
STACKED caches of all layers (None: uncached), of which the mixer reads and
returns its own part (models/cache.py), `layer` the layer's index in them
(among its kind where leaves and pools are per kind), `ctx` what the pass
fixed before its layer loop; `out` is the block's output ahead of the
residual add.  A new kind of mixer is a module here, a row of `MIXERS` and
its line of `ModelConfig.mixer_of`.  No mixer is wrapped in a jit or a scope
of its own: an inner function's name and every scope are in the lowered text
the pins hold (tests/test_lowered_pins.py).
"""

from __future__ import annotations

from collections import namedtuple

from . import gqa, latent, state

# What one forward pass fixes before its layer loop: the config, {kind: (cos,
# sin)} ((None, None): a kind that does not rotate), the pass's own arguments
# and, for a recurrent state, its StatePlan (None: no state) and the slot
# accessors AS THE FORWARD PASS'S MODULE NAMES THEM (a check swaps
# `llama._write_state`).
MixContext = namedtuple("MixContext", (
    "cfg", "rope", "positions", "kv_valid", "cache_positions", "paged",
    "mesh", "plan", "read_state", "write_state"))

# A row: `mix`, the scope the layer's residual add sits under, and whether
# `mix` reads `ctx.rope[kind]` (the pass then builds the kind's table).
Mixer = namedtuple("Mixer", ("mix", "scope", "positional"))

# keyed by `ModelConfig.mixer_of(kind)`
MIXERS = {
    "gqa": Mixer(gqa.mix, "attn_out", True),
    "latent": Mixer(latent.mix, "attn_out", True),
    "conv": Mixer(state.mix_conv, "conv_proj", False),
    "delta": Mixer(state.mix_delta, "kda_proj", False),
    "ssd": Mixer(state.mix_ssd, "attn_out", True),
    "mamba2": Mixer(state.mix_mamba2, "ssd_proj", False),
}
