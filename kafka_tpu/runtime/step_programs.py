"""The jitted step programs: everything the engine runs on the device.

One direction only: `engine -> step_programs -> models / ops.sampling`.
Nothing here imports the engine or sees a request; a program is a pure
function of device arrays, built for one model config and one pool geometry.
Three things have their one home here:

* **Names and the cache.** A program is jitted under the name its
  /debug/compiles label gives and kept in a process-wide cache.
* **The paged index plan.** A page table and positions become the flat slot
  indices of a `PagedView`: the pool's addressing format, for one token per
  lane (decode), a prefill chunk (one sequence, or a row per lane) and K+1
  tokens per lane (speculative verify).
* **The state plan** of a model with a recurrent state (`cfg.has_state`,
  models/hybrid.py): which state slot each lane reads and writes rides the
  `PagedView` as a `StatePlan`.  Decode's lanes are slots 0 .. B - 1; a
  prefill program takes its lanes' slots and snapshot slots as trailing
  arguments (where a vision model's take their override arrays), and
  `state_copy` restores a snapshot into a lane's slot.
* **One builder per program kind** (`StepPrograms`).  The decode-side
  programs take the lane arrays as one `Lanes` pytree and the on-device
  grammar automaton as one optional `Fsm` pytree: `None` traces the plain
  program, a tuple the `_fsm` one, whose result ends with the automaton's
  new state and budget.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.cache import KVCache, PagedView, StatePlan
from ..models.config import DELTA, ModelConfig
from ..models.ffn import experts_int8, moe_dispatch_form
from ..models.llama import forward
from ..models.mixers.index import INDEX_WALK_KEYS, walk_pages
from ..models.mixers.latent import prefill_walk_pages
from ..models.mixers.state import state_launch_forms
from ..ops.attention import decode_walk_pages, shared_walk_trips
from ..ops.pallas import ssd as ssd_kernels
from ..ops.pallas.gated_delta import chunk_rows
from ..ops.pallas.paged_attention import RING, step_pages
from ..ops.sampling import (
    SamplingParams,
    grammar_advance,
    grammar_allowed_mask,
    sample_tokens_per_slot,
)
from . import compile_log, program_store

# Compiled step functions are cached per (model cfg, engine shape) so that
# multiple engine instances (tests, restarts) reuse compilations.
_PROGRAMS: Dict[Tuple, Callable] = {}


def clear() -> None:
    """Forget every cached program: the next engine compiles its own."""
    _PROGRAMS.clear()


def program_name(label: str) -> str:
    """Function name a step program is jitted under, from its
    /debug/compiles label: `multi_decode[16]_fsm` -> `fn_multi_decode_16_fsm`
    (a device trace shows the module `jit_fn_multi_decode_16_fsm`).  The
    benchmark's trace readers find decode programs by the `jit_body` /
    `jit_fn` prefixes (benchmarks/layer_metrics/decode_step_dev_ms.py), and
    tell the single decode step from the rest by `jit_body`: it is
    `body_decode`, every other name begins `fn_`.  (Not the bare `body` it
    was before the scopes: the persistent compile cache keys a program
    without its metadata, so an executable compiled by a tree without
    scopes would be reused under the same name, and its ops would carry
    no scope in a trace.)"""
    if label == "decode":
        return "body_decode"
    return "fn_" + label.replace("[", "_").replace("]", "")


def _jit_step(label: str, fn: Callable, key: Any = None,
              recipe: Optional[Tuple] = None) -> Callable:
    """Jit one engine step program (k/v pools donated) under the name its
    label gives and hand it to the compile observatory, so compile records
    and trace module names cannot drift apart.  Where the persistent compile
    cache is on, the program store stands between the two
    (program_store.wrap: `key`, what the program is cached under in
    `_PROGRAMS`, goes into its key, and `recipe`, how to build `fn` again,
    beside its entry): the executable of a program it holds is loaded, not
    traced."""
    fn.__name__ = fn.__qualname__ = program_name(label)
    jitted = jax.jit(fn, donate_argnums=program_store.DONATED)
    if key is not None:
        jitted = program_store.wrap(label, jitted, key, recipe)
    return compile_log.instrument(label, jitted)


class Lanes(NamedTuple):
    """The per-lane device arrays of a decode-side program: [B] each, but
    the [B, P] page table."""

    page_table: jnp.ndarray
    last_tokens: jnp.ndarray
    seq_lens: jnp.ndarray
    active: jnp.ndarray
    temps: jnp.ndarray
    top_ks: jnp.ndarray
    top_ps: jnp.ndarray
    seeds: jnp.ndarray


class Fsm(NamedTuple):
    """On-device grammar lanes: mask from the lane's FSM state, advance it
    by the sampled token, decrement the wrap-up budget."""

    state: jnp.ndarray        # [B], -1 = unconstrained
    g_idx: jnp.ndarray        # [B]
    budget: jnp.ndarray       # [B]
    token_class: jnp.ndarray  # [G, V]
    trans: jnp.ndarray        # [S, C]
    dist: jnp.ndarray         # [S]
    slack: jnp.ndarray        # []

    @property
    def key(self) -> Tuple[int, int, int]:
        """The tables' padded shape, what an fsm program is built for (the
        tables grow geometrically, so one retraces O(log states) times)."""
        return (*self.trans.shape, self.token_class.shape[0])


# ----------------------------------------------------------------------
# the paged index plan: each returns (positions, PagedView)
# ----------------------------------------------------------------------


def _window(page_table, ps: int):
    """(read_idx, kv_positions) [B, C]: each lane's pages, slot by slot."""
    B, P = page_table.shape
    read_idx = (
        page_table[:, :, None] * ps + jnp.arange(ps)[None, None, :]
    ).reshape(B, P * ps)
    kv_positions = jnp.broadcast_to(
        jnp.arange(P * ps)[None, :], (B, P * ps))
    return read_idx, kv_positions


def decode_plan(page_table, seq_lens, active, ps: int):
    """One new token per lane, at position seq_len."""
    with jax.named_scope("step_ctl"):
        B = page_table.shape[0]
        positions = seq_lens[:, None]
        write_page = page_table[jnp.arange(B), seq_lens // ps]
        write_idx = (write_page * ps + seq_lens % ps)[:, None]
        # inactive slots scribble on the trash page
        write_idx = jnp.where(
            active[:, None], write_idx, (seq_lens % ps)[:, None])
        read_idx, kv_positions = _window(page_table, ps)
        kv_valid = (kv_positions <= seq_lens[:, None]) & active[:, None]
        paged = PagedView(
            write_idx, read_idx, kv_positions, kv_valid,
            page_table=page_table, seq_lens=seq_lens, page_size=ps)
    return positions, paged


def chunk_plan(page_rows, starts, chunk_lens, lane_active, S: int, ps: int):
    """A prefill chunk of up to S tokens per lane, lane i at positions
    starts[i]..; rows past chunk_lens[i] and inactive lanes write the trash
    page."""
    with jax.named_scope("step_ctl"):
        local = jnp.arange(S)[None, :]
        pos = starts[:, None] + local  # [W, S]
        in_chunk = (local < chunk_lens[:, None]) & lane_active[:, None]
        page_idx = jnp.take_along_axis(page_rows, pos // ps, axis=1)
        write_idx = jnp.where(in_chunk, page_idx * ps + pos % ps, local % ps)
        read_idx, kv_positions = _window(page_rows, ps)
        kv_valid = (
            kv_positions < (starts + chunk_lens)[:, None]
        ) & lane_active[:, None]
        # (chunk_len with no `start`: no attention path reads it; the routed
        # block leaves the rows past it out of its groups)
        paged = PagedView(
            write_idx, read_idx, kv_positions, kv_valid,
            page_table=page_rows, page_size=ps,
            chunk_len=jnp.where(lane_active, chunk_lens, 0))
    return pos, paged


def prefill_plan(page_row, start, chunk_len, S: int, ps: int):
    """The single-sequence form of `chunk_plan`: its one-row case (tests/
    test_step_programs.py holds the two equal), spelled with scalar bounds,
    which the view carries for the flash prefill kernel.  Kept beside it
    because the one-row case lowers to other gathers: another executable
    for every prefill[b]."""
    with jax.named_scope("step_ctl"):
        local = jnp.arange(S)
        positions = (start + local)[None, :]
        in_chunk = local < chunk_len
        write_page = page_row[(start + local) // ps]
        write_idx = jnp.where(
            in_chunk, write_page * ps + (start + local) % ps, local % ps,
        )[None, :]
        C = page_row.shape[0] * ps
        read_idx = (
            page_row[:, None] * ps + jnp.arange(ps)[None, :]
        ).reshape(1, C)
        kv_positions = jnp.arange(C)[None, :]
        kv_valid = kv_positions < (start + chunk_len)
        paged = PagedView(
            write_idx, read_idx, kv_positions, kv_valid,
            page_table=page_row[None, :], page_size=ps,
            start=start, chunk_len=chunk_len)
    return positions, paged


def verify_plan(page_table, seq_lens, cand_lens, active, S: int, ps: int):
    """S = K+1 tokens per lane, [last_token, c_1..c_K] at positions
    seq_len..seq_len+K; positions past cand_len are garbage lanes' padding
    and write the trash page."""
    with jax.named_scope("step_ctl"):
        local = jnp.arange(S)[None, :]
        pos = seq_lens[:, None] + local  # [B, S]
        in_run = (local <= cand_lens[:, None]) & active[:, None]
        page_idx = jnp.take_along_axis(
            page_table, jnp.minimum(pos // ps, page_table.shape[1] - 1),
            axis=1)
        write_idx = jnp.where(in_run, page_idx * ps + pos % ps, local % ps)
        read_idx, kv_positions = _window(page_table, ps)
        kv_valid = (
            kv_positions <= (seq_lens + cand_lens)[:, None]) & active[:, None]
        paged = PagedView(
            write_idx, read_idx, kv_positions, kv_valid,
            page_table=page_table, seq_lens=seq_lens, page_size=ps,
            chunk_len=cand_lens + 1)
    return pos, paged


# ----------------------------------------------------------------------
# the programs, one function per kind; each returns a fresh closure
# (`_jit_step` sets the name on the function it is given)
# ----------------------------------------------------------------------


def _with_state(cfg, paged, lens, slots=None, snaps=None, starts=None):
    """`paged` with the StatePlan of a pass whose lanes hold `lens` real rows
    (models/cache.StatePlan; `paged` itself for a model without a state).
    Decode: lane i in slot i.  Prefill: lane i reads and writes `slots[i]`,
    from zeros where it starts at position 0, and leaves a snapshot in
    `snaps[i]`."""
    if not cfg.has_state:
        return paged
    with jax.named_scope("step_ctl"):
        if slots is None:
            return paged._replace(state=StatePlan(lens=lens))
        return paged._replace(state=StatePlan(
            lens=lens, src=slots, dst=slots, snap=snaps, fresh=starts == 0))


def _moe_form(cfg, mesh, rows: int, int8: bool) -> Optional[str]:
    """The form the routed blocks of a pass of `rows` rows trace to, over
    experts that are `int8` or not (models/ffn.py moe_dispatch_form, the
    rule `_moe_block` itself asks); None for a model with no routed
    block."""
    if not cfg.is_moe:
        return None
    return moe_dispatch_form(
        rows, cfg.num_experts, cfg.num_experts_per_tok,
        mesh is not None and mesh.size > 1, cfg.num_router_experts, int8)


def _tallies(cfg, mesh, rows: int, int8: bool) -> bool:
    """Whether a decode program of `rows` lanes counts its routed layers'
    reads and picks ITSELF (`forward`'s `expert_reads`): where they dispatch
    by token (an idle lane picks none and an expert may go unread) and where
    the model holds a SHARE of the experts in the dense form (which picks
    fell on an expert held here only the device knows).  Elsewhere every
    held expert is read and every pick is held: the host knows both."""
    form = _moe_form(cfg, mesh, rows, int8)
    return form == "token" or (
        form == "dense" and bool(cfg.num_experts_routed))


def _forward(cfg, mesh, params, tokens, positions, k_pool, v_pool, paged,
             vis=(), expert_reads=False):
    """The model over a paged pool -> (logits, KVCache[, experts read]).
    `vis` = (embed override, on-mask), present iff cfg.vision."""
    if mesh is not None and mesh.shape.get("pp", 1) > 1:
        from ..parallel.pipeline import pp_forward_paged

        logits, k_new, v_new = pp_forward_paged(
            params, cfg, tokens, positions, k_pool, v_pool, paged, mesh)
        return logits, KVCache(k_new, v_new)
    return forward(
        params, cfg, tokens, positions,
        kv_cache=KVCache(k_pool, v_pool), paged=paged, mesh=mesh,
        embed_override=vis[0] if vis else None,
        override_on=vis[1] if vis else None,
        expert_reads=expert_reads,
    )


def _decode_fn(cfg: ModelConfig, mesh: Any, ps: int):
    """One decode step as a pure function of device state; the single-step
    program, and the body of the fused multi-step scan.  Returns, last, i32
    [3], the held experts its routed layers read, their picks that fell on
    an expert held here and all their picks, where the program counts them
    (`_tallies` at this many lanes: an idle lane then picks none), else None:
    every held expert and every pick, which the host knows."""

    def body(params, k_pool, v_pool, lanes, allowed_mask, forced=None,
             fsm=None):
        (page_table, last_tokens, seq_lens, active, temps, top_ks,
         top_ps, seeds) = lanes
        positions, paged = decode_plan(page_table, seq_lens, active, ps)
        paged = _with_state(cfg, paged, active.astype(jnp.int32))
        reads = None
        if _tallies(cfg, mesh, page_table.shape[0], cfg.is_moe
                    and experts_int8(params["layers"])):
            with jax.named_scope("step_ctl"):
                paged = paged._replace(chunk_len=active.astype(jnp.int32))
            logits, cache, reads = _forward(
                cfg, mesh, params, last_tokens[:, None], positions,
                k_pool, v_pool, paged, expert_reads=True)
        else:
            logits, cache = _forward(
                cfg, mesh, params, last_tokens[:, None], positions,
                k_pool, v_pool, paged)
        if fsm is not None:
            with jax.named_scope("fsm"):
                gmask = grammar_allowed_mask(
                    fsm.state, fsm.g_idx, fsm.budget, active,
                    fsm.token_class, fsm.trans, fsm.dist, fsm.slack)
                allowed_mask = (
                    gmask if allowed_mask is None else allowed_mask & gmask)
        with jax.named_scope("sample"):
            logits = logits[:, 0]
            keys = jax.vmap(
                lambda s, p: jax.random.fold_in(jax.random.key(s), p)
            )(seeds, seq_lens)
            toks = sample_tokens_per_slot(
                logits, SamplingParams(temps, top_ks, top_ps), keys,
                allowed_mask)
            if forced is not None:
                # grammar-forced lanes: the next token is host-known
                # (singleton mask) — overriding the sample here replaces a
                # [B, V] mask upload per chained dispatch with a [B] int32
                forced_tok, forced_on = forced
                toks = jnp.where(forced_on, forced_tok, toks)
        with jax.named_scope("step_ctl"):
            next_lens = seq_lens + active.astype(jnp.int32)
        if fsm is None:
            return cache.k, cache.v, toks, next_lens, reads
        with jax.named_scope("fsm"):
            new_state = grammar_advance(
                fsm.state, fsm.g_idx, toks, active, fsm.token_class,
                fsm.trans)
            new_budget = fsm.budget - active.astype(jnp.int32)
        return (cache.k, cache.v, toks, next_lens, new_state, new_budget,
                reads)

    return body


def _multi_decode_fn(cfg: ModelConfig, mesh: Any, ps: int, steps: int):
    body = _decode_fn(cfg, mesh, ps)

    def fn(params, k_pool, v_pool, lanes, fsm=None):
        fs0 = () if fsm is None else (fsm.state, fsm.budget)

        def one(carry, _):
            kp, vp, last, lens, *fs = carry
            # (the step's experts read, or None, stacked beside its tokens)
            kp, vp, toks, lens, *fs, reads = body(
                params, kp, vp,
                lanes._replace(last_tokens=last, seq_lens=lens), None,
                fsm=(None if fsm is None else
                     fsm._replace(state=fs[0], budget=fs[1])),
            )
            return (kp, vp, toks, lens, *fs), (toks, reads)

        with jax.named_scope("step_ctl"):
            (kp, vp, last, lens, *fs), (toks_seq, reads) = jax.lax.scan(
                one,
                (k_pool, v_pool, lanes.last_tokens, lanes.seq_lens, *fs0),
                None, length=steps,
            )
        return (kp, vp, toks_seq, last, lens, *fs, reads)

    return fn


def _verify_fn(cfg: ModelConfig, mesh: Any, ps: int, K: int):
    S = K + 1
    if cfg.has_state:
        raise NotImplementedError(
            "speculative verify cannot roll a recurrent state back")

    def fn(params, k_pool, v_pool, lanes, cands, cand_lens, fsm=None):
        (page_table, last_tokens, seq_lens, active, temps, top_ks,
         top_ps, seeds) = lanes
        B = page_table.shape[0]
        with jax.named_scope("step_ctl"):
            toks_in = jnp.concatenate([last_tokens[:, None], cands], axis=1)
        pos, paged = verify_plan(
            page_table, seq_lens, cand_lens, active, S, ps)
        logits, cache = forward(
            params, cfg, toks_in, pos,
            kv_cache=KVCache(k_pool, v_pool), paged=paged, mesh=mesh,
        )  # [B, S, V]
        V = logits.shape[-1]
        rep = lambda x: jnp.repeat(x, S)
        allowed_flat = None
        states_arr = None
        if fsm is not None:
            # FSM state BEFORE each sample position: state_j is the
            # automaton after the first j candidate tokens (exactly the
            # states sequential decode would thread); positions past
            # cand_len walk garbage that acceptance never reads.
            with jax.named_scope("fsm"):
                sts = [fsm.state]
                for j in range(K):
                    sts.append(grammar_advance(
                        sts[-1], fsm.g_idx, cands[:, j], active,
                        fsm.token_class, fsm.trans))
                states_arr = jnp.stack(sts, axis=1)  # [B, S]
                masks = [
                    grammar_allowed_mask(
                        sts[j], fsm.g_idx, fsm.budget - j, active,
                        fsm.token_class, fsm.trans, fsm.dist, fsm.slack)
                    for j in range(S)
                ]
                allowed_flat = jnp.stack(masks, axis=1).reshape(B * S, V)
        with jax.named_scope("sample"):
            # per-(seed, position) keys — IDENTICAL to the keys the
            # sequential decode path folds for these positions
            keys = jax.vmap(
                lambda s, prow: jax.vmap(
                    lambda p: jax.random.fold_in(jax.random.key(s), p)
                )(prow)
            )(seeds, pos)
            samples = sample_tokens_per_slot(
                logits.reshape(B * S, V),
                SamplingParams(rep(temps), rep(top_ks), rep(top_ps)),
                keys.reshape(B * S), allowed_flat,
            ).reshape(B, S)
        with jax.named_scope("step_ctl"):
            # longest exactly-matching candidate prefix, then the bonus
            # token (the sample after the last accepted candidate)
            good = (samples[:, :K] == cands) & (
                jnp.arange(K)[None, :] < cand_lens[:, None])
            m = jnp.sum(jnp.cumprod(good.astype(jnp.int32), axis=1), axis=1)
            adv = jnp.where(active, m + 1, 0)
            # rejected-tail KV rolled back here
            new_lens = seq_lens + adv
            bonus = jnp.take_along_axis(samples, m[:, None], axis=1)[:, 0]
            new_last = jnp.where(active, bonus, last_tokens)
            out = jnp.concatenate([samples, m[:, None]], axis=1)  # [B, S+1]
        if fsm is None:
            return cache.k, cache.v, out, new_last, new_lens
        # rejected-tail FSM rollback: the state the lane keeps is the one
        # reached through the ACCEPTED prefix (states_arr at m), advanced
        # once by the bonus token — the exact mirror of the seq_lens clamp
        with jax.named_scope("fsm"):
            s_m = jnp.take_along_axis(states_arr, m[:, None], axis=1)[:, 0]
            new_fsm = grammar_advance(
                s_m, fsm.g_idx, bonus, active, fsm.token_class, fsm.trans)
            new_budget = fsm.budget - adv
        return (cache.k, cache.v, out, new_last, new_lens, new_fsm,
                new_budget)

    return fn


def _prefill_fn(cfg: ModelConfig, mesh: Any, ps: int, bucket: int):
    def fn(params, k_pool, v_pool, page_row, chunk, start, chunk_len,
           temp, top_k, top_p, seed, allowed_mask, *vis):
        # [1, S] shapes throughout; `start` supports chunked prefill and
        # prefix-cache hits (resume mid-prompt).  `vis` = (ov [S, H],
        # ov_on [S]) embed-override arrays, present iff cfg.vision —
        # per-engine the arity is constant, so one compile either way.
        # A model with a recurrent state takes (slot, snap) there instead:
        # the lane's state slot and where to leave a snapshot.
        S = bucket
        positions, paged = prefill_plan(page_row, start, chunk_len, S, ps)
        if cfg.has_state:
            (slot, snap), vis = vis, ()
            paged = _with_state(cfg, paged, chunk_len[None], slot[None],
                                snap[None], start[None])
        logits, cache = _forward(
            cfg, mesh, params, chunk[None, :], positions, k_pool, v_pool,
            paged, tuple(v[None] for v in vis))
        with jax.named_scope("sample"):
            # (a hybrid decoder's prefill returns the last real row alone)
            last = 0 if cfg.has_state else jnp.clip(chunk_len - 1, 0, S - 1)
            final_logits = logits[0, last][None, :]  # [1, V]
            sp = SamplingParams(
                temperature=temp[None], top_k=top_k[None], top_p=top_p[None])
            key = jax.random.fold_in(
                jax.random.key(seed[0]), start + chunk_len - 1)
            tok = sample_tokens_per_slot(
                final_logits, sp, key[None], allowed_mask)
        return cache.k, cache.v, tok[0]

    return fn


def _batched_prefill_fn(cfg: ModelConfig, mesh: Any, ps: int, bucket: int):
    def fn(params, k_pool, v_pool, page_rows, chunks, starts,
           chunk_lens, temps, top_ks, top_ps, seeds, lane_active, *vis):
        # vis = (ov [W, S, H], ov_on [W, S]) iff cfg.vision; (slots [W],
        # snaps [W]) iff cfg.has_state
        S = bucket
        pos, paged = chunk_plan(
            page_rows, starts, chunk_lens, lane_active, S, ps)
        if cfg.has_state:
            (slots, snaps), vis = vis, ()
            paged = _with_state(
                cfg, paged, jnp.where(lane_active, chunk_lens, 0), slots,
                snaps, starts)
        logits, cache = forward(
            params, cfg, chunks, pos,
            kv_cache=KVCache(k_pool, v_pool), paged=paged, mesh=mesh,
            embed_override=vis[0] if vis else None,
            override_on=vis[1] if vis else None,
        )
        with jax.named_scope("sample"):
            last = (jnp.zeros_like(chunk_lens) if cfg.has_state
                    else jnp.clip(chunk_lens - 1, 0, S - 1))
            final_logits = jnp.take_along_axis(
                logits, last[:, None, None], axis=1)[:, 0]  # [W, V]
            keys = jax.vmap(
                lambda s, p: jax.random.fold_in(jax.random.key(s), p)
            )(seeds, starts + chunk_lens - 1)
            toks = sample_tokens_per_slot(
                final_logits, SamplingParams(temps, top_ks, top_ps), keys,
                None)
        return cache.k, cache.v, toks

    return fn


def fn_state_copy(v_pool, src, dst):
    """v_pool with state slot `dst` a copy of slot `src`, every state leaf
    and layer (the rows leaf "v" is passed through)."""
    with jax.named_scope("step_ctl"):
        return {name: leaf if name == "v" else leaf.at[:, dst].set(leaf[:, src])
                for name, leaf in v_pool.items()}


class StepPrograms:
    """The step programs of one engine: one model config on one mesh over
    one pool geometry.  Each builder returns the jitted program: from
    `built`, this engine's record of what it has been handed (and so of
    what warm-up compiled), else from the process-wide cache, else by
    jitting it.  A decode-side builder is handed the `Fsm` the program
    will be called with, or `None` for the plain program.  `int8_experts`:
    the params the programs will be handed hold their routed experts as
    int8 (`models/ffn.py experts_int8`: what the programs themselves see
    when traced)."""

    def __init__(self, cfg: ModelConfig, mesh: Any, page_size: int,
                 max_batch: int, max_pages_per_seq: int,
                 int8_experts: bool = False, int8_kv: bool = False):
        self.cfg, self.mesh, self.ps = cfg, mesh, page_size
        self.B, self.P = max_batch, max_pages_per_seq
        self.int8_experts, self.int8_kv = int8_experts, int8_kv
        self.built: Dict[Tuple[str, Optional[Tuple]], Callable] = {}

    def _program(self, label: str, key: Tuple, fsm_key: Optional[Tuple],
                 make: Callable[..., Callable], *args) -> Callable:
        fn = self.built.get((label, fsm_key))
        if fn is None:
            fn = _PROGRAMS.get(key)
            if fn is None:
                # (a program over a mesh is left to the jit: no store key)
                fn = _PROGRAMS[key] = _jit_step(
                    label, make(self.cfg, self.mesh, self.ps, *args),
                    None if self.mesh is not None else
                    (key, self.int8_experts, self.int8_kv),
                    (make.__name__, self.cfg, self.ps, args))
            self.built[(label, fsm_key)] = fn
        return fn

    def _geometry(self) -> Tuple:
        return self.cfg, self.ps, self.P * self.ps, self.B, self.mesh

    def _decode_walk_pages(self) -> int:
        """Pages a trip of the XLA decode walk reads a lane; 0 where decode
        does not walk in XLA: a Pallas kernel, a latent model's own read,
        or pp (no page table in the view)."""
        mesh = self.mesh
        if (self.cfg.attention_backend != "xla" or self.cfg.is_latent
                or (mesh is not None and mesh.shape.get("pp", 1) > 1)):
            return 0
        return decode_walk_pages(self.P, self.ps)

    def decode_keys(self, max_len: int, steps: int) -> Tuple[int, int]:
        """(keys walked, keys of the static windows) over the B lanes of
        `steps` decode steps whose longest active lane holds `max_len`
        tokens before the first: what the XLA decode walk
        (ops/attention.py paged_decode_walk) gathers a layer, by the bound
        its device loop computes, beside lanes x max_pages_per_seq x
        page_size.  (0, 0) where decode does not walk in XLA."""
        ck = self._decode_walk_pages() * self.ps
        if not ck:
            return 0, 0
        chunks = sum(-(-(max_len + i + 1) // ck) for i in range(steps))
        return self.B * chunks * ck, self.B * steps * self.P * self.ps

    def decode_keys_shared(self, lanes, steps: int) -> int:
        """Of `decode_keys`' walked keys, those of the walk's SHARED trips
        (read once for every lane, counted for the B lanes as `walked`
        counts them): `lanes` [(pages, tokens held before the first step)]
        of the dispatch's active lanes, by the device's own arithmetic
        (ops/attention.py common_pages, paged_decode_walk).  0 where decode
        does not walk in XLA."""
        cp = self._decode_walk_pages()
        ck = cp * self.ps
        return cp and self.B * ck * sum(
            shared_walk_trips(lanes, steps, self.P, cp, ck))

    def decode_steps(self, lanes, steps: int) -> Tuple[int, int, int, int]:
        """(whole softmax steps the Pallas decode walk fetches, those of
        them fetched as ONE run copy a pool, every softmax step it walks,
        those of them whose copies were started before their lane's program
        began) over `steps` decode steps of the call's `lanes` (slot by
        slot a kv_cache.SequencePages, its `length` the tokens held before
        the first, or None: not in this dispatch): a global layer's walk,
        one layer's worth, by the kernel's own arithmetic
        (ops/pallas/paged_attention.py _decode_kernel: a lane holding n
        tokens walks n // step_keys whole steps and a last one; the call's
        lanes are one stream of steps whose copies start RING - 1 steps
        ahead, so every lane but the call's first finds its first RING - 1
        steps started).  Zeros where no global layer walks in that kernel:
        the `xla` backend, pp, a model with an indexer (its full layers read
        chosen rows), an int8 pool (its kernel keeps the older walk)."""
        mesh = self.mesh
        if (self.cfg.attention_backend != "pallas" or self.cfg.index_topk
                or self.int8_kv
                or (mesh is not None and mesh.shape.get("pp", 1) > 1)):
            return 0, 0, 0, 0
        # (the wrappers' default chunk; a GQA pool's merged row sizes the step)
        cfg = self.cfg
        row_bytes = 0 if cfg.is_latent else (
            cfg.num_kv_heads * cfg.head_dim
            * jnp.dtype(cfg.activation_dtype).itemsize)
        _, sp = step_pages(self.P, 8, self.ps, row_bytes)
        keys = sp * self.ps
        walked = run = every = ahead = 0
        for lane, seq in enumerate(lanes):
            if seq is None:
                continue
            n, end = seq.length, seq.length + steps
            for whole in range(n // keys, (end - 1) // keys + 1):
                # the decode steps that find `whole` whole steps behind them
                passes = min(end, (whole + 1) * keys) - max(n, whole * keys)
                walked += passes * whole
                run += passes * seq.run_steps(sp, whole)
                every += passes * (whole + 1)
                ahead += passes * min(whole + 1, RING - 1) * (lane > 0)
        return walked, run, every, ahead

    def index_keys(self, lengths, steps: int) -> Tuple[int, int]:
        """(keys scored, keys kept) by ONE layer's indexer over `steps`
        decode steps of lanes holding `lengths` tokens before the first: a
        step scores the lane's whole context, its own new row included, and
        keeps at most `index_topk` of it (models/mixers/index.py
        _paged_index_choice).  (0, 0) for a model without an indexer."""
        topk = self.cfg.index_topk
        if not topk:
            return 0, 0
        scored = sum(n + i + 1 for n in lengths for i in range(steps))
        kept = sum(min(n + i + 1, topk) for n in lengths
                   for i in range(steps))
        return scored, kept

    def index_keys_shared(self, lanes, steps: int) -> int:
        """Of `index_keys`' scored keys, those scored through the walk's
        SHARED trips: `lanes` [(pages, tokens held before the first step)]
        of the dispatch's active lanes, by the device's own arithmetic
        (ops/attention.py common_pages, models/mixers/index.py
        _paged_index_scores): the leading columns in which every lane's
        page-table row names one page, whole trips of them, up to each
        lane's live context."""
        if not self.cfg.index_topk:
            return 0
        cp = walk_pages(self.P, self.ps, INDEX_WALK_KEYS)
        ck = cp * self.ps
        return sum(min(n + i + 1, own * ck) for i, own in enumerate(
            shared_walk_trips(lanes, steps, self.P, cp, ck)) for _, n in lanes)

    def prefill_walk_trips(self, spans, width: int,
                           bucket: int) -> Tuple[int, int]:
        """(trips, trips the Pallas kernel folds) of ONE prefill launch of
        `width` lanes x `bucket` rows whose active lanes hold `spans`
        [(start, chunk_len)]: what models/mixers/latent.py _latent_prefill_walk
        loops, by the bounds its device loop computes, summed over the
        layers that walk (a full layer from key 0 to the longest lane's last
        key, a sliding layer from the chunk that holds the first window's
        first key).  Equal where the fold runs in the kernel, the second 0
        where it runs in XLA, (0, 0) where prefill does not walk: a model
        that is not latent."""
        cfg = self.cfg
        if not (cfg.is_latent and spans):
            return 0, 0
        kernel = cfg.attention_backend == "pallas"
        cp = prefill_walk_pages(self.P, self.ps, width * bucket, kernel)
        ck = cp * self.ps
        trips = min(-(-max(s + n for s, n in spans) // ck), -(-self.P // cp))
        total = 0
        for kind in cfg.kinds:
            window, first = cfg.window_of(kind), 0
            if window is not None:
                lo = min(s for s, _ in spans) - window + 1
                first = min(max(lo, 0) // ck, trips)
            total += cfg.layers_of(kind) * (trips - first)
        return total, total if kernel else 0

    def delta_chunk_trips(self, lanes: int, bucket: int) -> int:
        """Chunks the gated-delta prefill kernel loops over ONE launch of
        `bucket` rows whose `lanes` active lanes it computes, by the grid the
        kernel itself is given (ops/pallas/gated_delta.chunk_rows), summed
        over the linear-attention layers; 0 for a model without them, on the
        XLA backend and for a bucket the kernel does not tile (the
        row-by-row scan runs)."""
        cfg = self.cfg
        n = cfg.layers_of(DELTA)
        if not n or cfg.attention_backend != "pallas":
            return 0
        rows = chunk_rows(bucket)
        return n * lanes * (bucket // rows) if rows else 0

    def delta_state_bytes(self, lanes: int, steps: int) -> int:
        """Bytes of delta state `steps` decode passes over `lanes` busy
        lanes read and wrote: every linear-attention layer's matrix a head,
        once in and once out (0 for a model without them)."""
        cfg = self.cfg
        return (2 * 4 * cfg.layers_of(DELTA) * cfg.delta_heads
                * cfg.delta_head_dim ** 2 * lanes * steps)

    def state_forms(self, rows: int, own_slots: bool):
        """{op: form} of ONE pass of `rows` rows a lane through the state
        layers (models/mixers/state.state_launch_forms: the rule each mixer
        itself asks when the program is traced; `own_slots`: a prefill
        launch, which names its lanes' slots); {} for a model without a
        state."""
        return state_launch_forms(self.cfg, rows, own_slots)

    def _ssd_layers(self) -> int:
        """Layers that hold an SSD mixer, beside attention or alone."""
        return self.cfg.state_layers if self.cfg.ssd_heads else 0

    def ssd_chunk_trips(self, lanes: int, bucket: int) -> int:
        """Chunks the SSD prefill kernel loops over ONE launch of `bucket`
        rows whose `lanes` active lanes it computes, by the grid the kernel
        itself is given (ops/pallas/ssd.chunk_rows), summed over the layers
        that hold one (beside attention or alone); 0 for a model without an
        SSD mixer, on the XLA backend and for a bucket the kernel does not
        tile (the row-by-row scan runs)."""
        cfg, n = self.cfg, self._ssd_layers()
        if not n or cfg.attention_backend != "pallas":
            return 0
        rows = ssd_kernels.chunk_rows(bucket)
        return n * lanes * (bucket // rows) if rows else 0

    def ssd_state_bytes(self, lanes: int, steps: int) -> int:
        """Bytes of SSD state `steps` decode passes over `lanes` busy lanes
        read and wrote: every layer's heads' states, once in and once out (0
        for a model without an SSD mixer)."""
        cfg = self.cfg
        return (2 * 4 * self._ssd_layers() * cfg.ssd_heads * cfg.ssd_head_dim
                * cfg.ssd_d_state * lanes * steps)

    def ssd_rows(self, lanes: int, bucket: int) -> int:
        """Rows x SSD layers of ONE launch of `lanes` lanes x `bucket` rows,
        padding included (0 for a model without an SSD mixer)."""
        return self._ssd_layers() * lanes * bucket

    def moe_dispatch(self, rows: int) -> Optional[str]:
        """"token" or "dense": the form the routed blocks of a pass of
        `rows` rows (lanes x rows a lane) trace to, by the rule
        models/ffn.py _moe_block itself asks (moe_dispatch_form); None
        for a model with no routed block."""
        return _moe_form(self.cfg, self.mesh, rows, self.int8_experts)

    def tallies(self, rows: int) -> bool:
        """Whether the decode and fused-decode programs of `rows` lanes
        return, last, their own count of experts read and picks ([3] a step,
        [steps, 3] fused: `_tallies`), or None and the host counts."""
        return _tallies(self.cfg, self.mesh, rows, self.int8_experts)

    def picks_a_pass(self, lanes: int) -> int:
        """Picks the routed layers of ONE decode pass make with `lanes` lanes
        active: lanes x top-k x routed layers (0: no routed block)."""
        return (lanes * self.cfg.num_experts_per_tok
                * self.cfg.routed_layers)

    def experts_held(self) -> int:
        """Held experts x routed layers: what one pass's routed blocks read
        in the dense form, and at most in the token form (0: no routed
        block)."""
        return self.cfg.num_experts * self.cfg.routed_layers

    def decode(self, fsm: Optional[Fsm] = None):
        """One token for every active lane: fn(params, k_pool, v_pool,
        lanes, allowed_mask [B, V] | None, forced ([B] tokens, [B] on-mask)
        | None, fsm) -> (k_pool', v_pool', toks [B], seq_lens', *fsm_out);
        fsm_out = (state', budget') iff `fsm`."""
        fsm_key = None if fsm is None else fsm.key
        label = "decode" if fsm is None else "decode_fsm"
        key = (label,) + self._geometry() + (
            () if fsm is None else (fsm_key,))
        return self._program(label, key, fsm_key, _decode_fn)

    def multi_decode(self, steps: int, fsm: Optional[Fsm] = None):
        """k fused decode steps in one dispatch (lax.scan over the step
        body, itself under the `step_ctl` scope so that a device trace
        tells this scan's plumbing from the layer scan's).  Sampling stays
        per-(seed, position) via the in-carry seq_lens, so outputs are
        token-identical to k single dispatches.
        fn(params, k_pool, v_pool, lanes, fsm) -> (k_pool', v_pool', toks
        [k, B], last [B], seq_lens [B], *fsm_out); an `Fsm` threads (state,
        budget) through the carry, so grammar lanes fuse too."""
        fsm_key = None if fsm is None else fsm.key
        label = f"multi_decode[{steps}]{'' if fsm is None else '_fsm'}"
        key = ("multi_decode",) + self._geometry() + (steps, fsm_key)
        return self._program(label, key, fsm_key, _multi_decode_fn, steps)

    def verify(self, K: int, fsm: Optional[Fsm] = None):
        """The speculative verify program: advance every lane 1..K+1 tokens
        in ONE dispatch (EngineConfig.speculative_k).
        fn(params, k_pool, v_pool, lanes, cands [B, K], cand_lens [B], fsm)
        -> (k_pool', v_pool', out [B, K+2], last', seq_lens', *fsm_out);
        out = the K+1 samples, then the accepted count.

        The fsm variant (built only once a grammar lane exists) lets
        CONSTRAINED lanes speculate: every position samples under the mask
        of the FSM state reached through the candidate prefix (a host-side
        sequential decode would compute exactly these states), the
        accepted count selects the state the lane actually reached, and
        the bonus token advances it once more — rejected-tail FSM rollback
        mirrors the seq_lens clamp.  Free lanes riding the fsm variant see
        all-True mask rows, which leave the sampler bit-identical to the
        plain program.

        A [B, K+1]-query forward over the paged pool — the batched-prefill
        attention formulation with per-query causal masking (on pallas
        backends models/llama.py routes it to the K+1-query paged verify
        kernel; elsewhere the page-granular XLA gather).  Non-proposing
        lanes run with cand_len 0: position 0 is their ordinary decode
        step and the K candidate positions write the trash page — same
        compiled program whatever the batch mix, nothing recompiles.

        Every position samples with the sequential decode path's OWN
        per-(seed, position) key, and acceptance keeps candidates exactly
        while `sample == candidate` — the emitted tokens ARE the
        sequential path's samples, so greedy is bit-identical and sampled
        output follows the target distribution at any temperature (the
        exact-match special case of Leviathan rejection sampling for a
        point-mass draft).  Rejected-tail KV is rolled back by clamping
        the returned seq_lens to the accepted length: stale KV past it is
        masked by kv_valid in later steps and overwritten when those
        positions are next written.
        """
        fsm_key = None if fsm is None else fsm.key
        label = "verify" if fsm is None else "verify_fsm"
        key = ("verify",) + self._geometry() + (K, fsm_key)
        return self._program(label, key, fsm_key, _verify_fn, K)

    def state_copy(self):
        """Restore a snapshot: fn(v_pool, src, dst) -> v_pool' (donated)
        with state slot `dst` a copy of `src`.  A state is mutated in place
        by every pass, pages are not, so a prefix hit copies."""
        fn = self.built.get(("state_copy", None))
        if fn is None:
            fn = _PROGRAMS.get("state_copy")
            if fn is None:
                # (one program whatever the model: shapes retrace it)
                fn = _PROGRAMS["state_copy"] = compile_log.instrument(
                    "state_copy", jax.jit(fn_state_copy, donate_argnums=(0,)))
            self.built[("state_copy", None)] = fn
        return fn

    def prefill(self, bucket: int):
        """One chunk of up to `bucket` prompt tokens of ONE sequence:
        fn(params, k_pool, v_pool, page_row [P], chunk [S], start,
        chunk_len, temp, top_k, top_p, seed [1], allowed_mask [1, V],
        *vis) -> (k_pool', v_pool', tok)."""
        key = ("prefill", self.cfg, bucket, self.ps, self.P * self.ps,
               self.P, self.mesh)
        return self._program(
            f"prefill[{bucket}]", key, None, _prefill_fn, bucket)

    def batched_prefill(self, bucket: int, width: int):
        """Prefill chunks for `width` sequences in ONE dispatch.

        Same index-plan semantics as the single-sequence program but with a
        leading lane axis: per-lane page rows, starts, and chunk lengths
        (inactive lanes write the trash page and sample garbage that the
        scheduler discards).  Used when several admissions share a bucket —
        one host dispatch instead of one per sequence, and the chunk
        matmuls batch.  The B>1 shape keeps the XLA attention formulation
        (the flash kernel's contract is single-sequence).
        fn(params, k_pool, v_pool, page_rows [W, P], chunks [W, S], starts,
        chunk_lens, temps, top_ks, top_ps, seeds, lane_active, *vis) ->
        (k_pool', v_pool', toks [W]).
        """
        key = ("bprefill", self.cfg, bucket, width, self.ps,
               self.P * self.ps, self.P, self.mesh)
        return self._program(
            f"bprefill[{bucket}x{width}]", key, None, _batched_prefill_fn,
            bucket)
