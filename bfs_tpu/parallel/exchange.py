"""Compressed frontier exchange for the sharded relay path (ROADMAP item 1).

The per-superstep exchange is where multi-chip BFS lives or dies
(Compression-and-Sieve, arXiv 1208.5542): a level-synchronous superstep
must hand every shard the global new-frontier bitmap, and shipping it
"flat" — every owned word, every superstep — costs the same wire bytes at
a 3-vertex tail frontier as at the peak level.  This module packages the
exchange as three arms behind one knob:

    BFS_TPU_EXCHANGE = auto | bitmap | delta | flat     (default auto)

  * ``flat`` — the uncompressed oracle: all-gather EVERY owned frontier
    word, padding included (``block/32`` words per shard).  Trivially
    correct, maximally dumb; the arm every other arm is parity-tested
    and byte-compared against.
  * ``bitmap`` — the sieved packed-bitmap arm: each shard gathers only
    its REAL owned words (the per-shard real-word table of
    :func:`bfs_tpu.parallel.sharded._own_word_table` — padding words are
    structurally zero and never ship), after the SIEVE has masked
    already-settled vertices out of the new-frontier bits (the
    ``& unreached`` / lexicographic-min improvement test every superstep
    body applies before packing — a settled vertex can never re-enter
    the wire).  Payload: ``kw`` words/shard, flat in the shard count.
  * ``delta`` — the word-list arm for SPARSE frontiers: each shard ships
    ``(word index, word value)`` pairs for its nonzero frontier words
    only, padded to a static budget of ``B`` entries (``2B`` u32 words on
    the wire vs ``kw``).  Selected per superstep by MEASURED frontier
    density: when any shard's nonzero-word count exceeds ``B`` the
    superstep falls back to the bitmap arm inside the same compiled
    program (one ``lax.cond`` whose predicate is a replicated ``pmax`` of
    the per-shard counts — every shard provably takes the same branch,
    and only the taken branch's collective executes, so the byte saving
    is real, not cosmetic).
  * ``auto`` — the delta arm with its density fallback, i.e. word-lists
    whenever the frontier is sparse enough to fit the budget and sieved
    bitmaps on the dense mid-levels.  ``delta`` differs from ``auto``
    only in the budget default: forced delta sizes ``B`` at ``kw`` so the
    word-list path runs on EVERY superstep (the parity/forcing arm);
    auto sizes it at ``kw/BFS_TPU_EXCHANGE_DIV`` (default 8) so taking
    the delta branch is always a >= 4x payload cut vs the flat arm.

Every arm returns ``(global_words, payload_bytes, arm_code)`` — the bytes
actually placed on the interconnect this superstep (``n * payload_words *
4``; the all-gather convention counts each shard's contribution once) and
the arm that shipped them, both accumulated device-side into the
telemetry level curves (obs/telemetry.py) so every capture reports
bytes-on-the-wire per level next to occupancy.

Wire format (docs/ARCHITECTURE.md has the worked example):

    bitmap payload   u32[kw]        shard s's real owned words, in
                                    ascending local word index (the
                                    own-word table order)
    delta payload    u32[2B]        [0:B)  = local COMPACT word indices of
                                    the nonzero words, ascending, padded
                                    with ``kw`` (= "no entry");
                                    [B:2B) = the matching word values
    flat payload     u32[block/32]  shard s's whole owned word range

Receivers scatter payloads back into the global standard-packed word
space (shard s's words at ``[s*block/32, (s+1)*block/32)``); the compact
arms resolve local word indices through the replicated own-word table.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import knobs
from .compat import pcast_varying

#: Arm codes, recorded per level in the telemetry exchange-arm
#: accumulator (0 = level not executed, same convention as the
#: direction codes).
EX_FLAT = 1
EX_BITMAP = 2
EX_DELTA = 3

EX_NAMES = {EX_FLAT: "flat", EX_BITMAP: "bitmap", EX_DELTA: "delta"}

EXCHANGE_MODES = ("auto", "bitmap", "delta", "flat")

#: Default density divisor for the auto arm's delta budget:
#: ``B = ceil(kw / div)`` compact entries -> ``2B ~ kw/4`` payload words
#: when taken, a >= 4x cut vs the flat arm's ``nw >= kw`` words.
DEFAULT_BUDGET_DIV = 8


@dataclass(frozen=True)
class ExchangeConfig:
    """Resolved exchange policy — hashable, so it keys programs and
    journal configs the way DirectionConfig does (a knob flip must map to
    a different compiled program and a different bench journal)."""

    mode: str = "auto"
    budget_div: int = DEFAULT_BUDGET_DIV

    def key(self) -> tuple:
        return (self.mode, int(self.budget_div))

    def delta_budget(self, kw: int) -> int:
        """Static word-list entry budget for a ``kw``-word compact space.
        Forced delta covers every frontier (``B = kw``: the word-list arm
        must be able to ship ANY superstep); auto/bitmap size it at the
        density divisor, and flat never builds a delta branch."""
        if self.mode == "delta":
            return int(kw)
        return max(1, math.ceil(int(kw) / int(self.budget_div)))


def resolve_exchange(mode: str | None = None) -> ExchangeConfig:
    """Env-resolved exchange config; an explicit ``mode`` argument wins
    over ``BFS_TPU_EXCHANGE``.  Unknown modes / non-positive divisors
    raise (same contract as resolve_direction: silently clamping a typo'd
    knob would change what a capture measured)."""
    if mode is None:
        mode = knobs.get("BFS_TPU_EXCHANGE")
    if mode not in EXCHANGE_MODES:
        raise ValueError(
            f"unknown exchange {mode!r}; use 'auto', 'bitmap', 'delta' or "
            "'flat'"
        )
    div = knobs.get("BFS_TPU_EXCHANGE_DIV")
    if div < 1:
        raise ValueError(f"BFS_TPU_EXCHANGE_DIV must be >= 1 (got {div})")
    return ExchangeConfig(mode=mode, budget_div=div)


def bitmap_gather(send, own_all, nw: int, axis_name: str):
    """THE bitmap wire move (single implementation — the standalone
    bitmap arm, the delta arm's density fallback AND the multi-source
    program's exchange all call this): all-gather each shard's compact
    real words and scatter them back into the global padded word space
    through the replicated own-word table (pad duplicates rewrite
    identical values, so the set is deterministic).

    ``send``: u32[..., kw] — this shard's compact words, optional leading
    batch (per-tree) dims.  Returns u32[..., n*nw]."""
    n = own_all.shape[0]
    if send.ndim == 1:
        gath = jax.lax.all_gather(send, axis_name)  # [n, kw]
    else:
        gath = jax.lax.all_gather(send, axis_name, axis=1)  # [s_l, n, kw]
    base = (jnp.arange(n, dtype=jnp.int32) * nw)[:, None]
    flat_idx = (own_all + base).reshape(-1)
    lead = send.shape[:-1]
    out = jnp.zeros((*lead, n * nw), jnp.uint32)
    return out.at[..., flat_idx].set(
        gath.reshape(*lead, -1), unique_indices=False
    )


# bfs_tpu: hot traced
def exchange_flat(send_words, n: int, axis_name: str):
    """The uncompressed oracle arm: all-gather the whole owned word range
    (padding words included).  ``send_words``: uint32[nw] local."""
    fw = jax.lax.all_gather(send_words, axis_name, tiled=True)
    nbytes = jnp.int32(4 * n * send_words.shape[-1])
    return fw, nbytes, jnp.int32(EX_FLAT)


def _bitmap_from_send(send, own_all, nw: int, axis_name: str):
    """:func:`bitmap_gather` plus the arm's byte/code accounting."""
    n, kw = own_all.shape
    fw = bitmap_gather(send, own_all, nw, axis_name)
    return fw, jnp.int32(4 * n * kw), jnp.int32(EX_BITMAP)


# bfs_tpu: hot traced
def exchange_bitmap(send_words, own_local, own_all, nw: int, axis_name: str):
    """Sieved compact-bitmap arm: gather the shard's REAL owned words only
    (``own_local``: int32[kw] local real-word indices; ``own_all``:
    int32[n, kw] every shard's table, replicated), then scatter them back
    into the global padded word space."""
    send = jnp.take(send_words, own_local, axis=-1)
    return _bitmap_from_send(send, own_all, nw, axis_name)


def _dedup_mask(own_local):
    """True at the first occurrence of each real word index (the own-word
    table right-pads by REPEATING the last real index; a duplicated tail
    word must not double-count in the delta arm's density measure or ship
    twice in its word list)."""
    kw = own_local.shape[0]
    first = jnp.ones((1,), bool)
    if kw == 1:
        return first
    return jnp.concatenate([first, own_local[1:] != own_local[:-1]])


# bfs_tpu: hot traced
def exchange_delta(
    send_words, own_local, own_all, nw: int, budget: int, axis_name: str,
    fits_axes=None,
):
    """Word-list arm with density fallback: ship ``(compact index, word)``
    pairs for nonzero words when every shard fits ``budget`` entries, else
    the bitmap arm — ONE replicated ``lax.cond``, only the taken branch's
    collective executes.

    ``fits_axes`` widens the density vote past the gather axis (the 2D
    grid votes over BOTH mesh axes so the whole machine takes one arm per
    superstep per axis — per-group votes would let different mesh rows
    diverge and break the replicated arm-schedule telemetry); ``None``
    keeps the 1D behavior (vote == gather axis)."""
    n = own_all.shape[0]
    kw = own_all.shape[1]
    send = jnp.take(send_words, own_local, axis=-1)
    live = (send != 0) & _dedup_mask(own_local)
    count = live.sum(dtype=jnp.int32)
    fits = jax.lax.pmax(
        count, axis_name if fits_axes is None else fits_axes
    ) <= jnp.int32(budget)

    def delta(send):
        idx = jnp.sort(
            jnp.where(live, jnp.arange(kw, dtype=jnp.int32), jnp.int32(kw))
        )[:budget]
        vals = jnp.where(
            idx < kw, send[jnp.clip(idx, 0, kw - 1)], jnp.uint32(0)
        )
        payload = jnp.concatenate([idx.astype(jnp.uint32), vals])
        gath = jax.lax.all_gather(payload, axis_name)  # [n, 2B]
        gi = gath[:, :budget].astype(jnp.int32)
        gv = gath[:, budget:]
        # Local compact index -> real owned word -> global padded word.
        word = jnp.take_along_axis(own_all, jnp.clip(gi, 0, kw - 1), axis=1)
        base = (jnp.arange(n, dtype=jnp.int32) * nw)[:, None]
        flat = jnp.where(gi < kw, word + base, jnp.int32(n * nw)).reshape(-1)
        out = jnp.zeros((n * nw,), jnp.uint32)
        fw = out.at[flat].set(gv.reshape(-1), mode="drop")
        return fw, jnp.int32(4 * n * 2 * budget), jnp.int32(EX_DELTA)

    def bitmap(send):
        return _bitmap_from_send(send, own_all, nw, axis_name)

    return jax.lax.cond(fits, delta, bitmap, send)


def make_exchange(cfg: ExchangeConfig, kw: int, nw: int, axis_name: str):
    """The per-superstep exchange closure for one resolved config:
    ``(send_words u32[nw], own_local, own_all) -> (global_words u32[n*nw],
    payload_bytes i32, arm_code i32)``.  Static per arm — the knob is part
    of the compiled program, selection inside it is the delta arm's
    density cond only."""
    if cfg.mode == "flat":
        return lambda w, ol, oa: exchange_flat(w, oa.shape[0], axis_name)
    if cfg.mode == "bitmap":
        return lambda w, ol, oa: exchange_bitmap(w, ol, oa, nw, axis_name)
    budget = cfg.delta_budget(kw)
    return lambda w, ol, oa: exchange_delta(
        w, ol, oa, nw, budget, axis_name
    )


def exchange_report(bytes_acc, arm_acc, cfg: ExchangeConfig, kw: int,
                    nw: int, num_shards: int,
                    num_levels: int | None = None) -> dict:
    """JSON-ready ``details.exchange`` from the host accumulators (post
    ``read_telemetry``): per-level bytes-on-the-wire, the per-level arm
    schedule, totals, and the flat-arm baseline the reduction is measured
    against (``n * nw * 4`` bytes per EXECUTED superstep — what the
    uncompressed exchange would have shipped for the SAME search).

    ``num_levels`` is the loop-exit superstep count — exact even when
    the search runs deeper than the TEL_SLOTS accumulator (slots clamp
    the per-level view, not the totals; a trimmed-slot baseline would
    undercount the flat comparison on >127-level searches)."""
    import numpy as np

    bv = np.asarray(bytes_acc, dtype=np.int64)
    av = np.asarray(arm_acc, dtype=np.int64)
    nz = np.flatnonzero(av)
    levels = int(nz[-1]) + 1 if nz.size else 0
    executed = (
        int(num_levels) if num_levels is not None
        else (levels - 1 if levels else 0)
    )
    schedule = [EX_NAMES.get(int(c), "none") for c in av[1:levels]]
    total = int(bv.sum())
    flat_total = int(executed * num_shards * nw * 4)
    out = {
        "arm": cfg.mode,
        "budget_words": int(cfg.delta_budget(kw)),
        "bytes_per_level": [int(x) for x in bv[1:levels]],
        "schedule": schedule,  # index i = the superstep that settled level i+1
        "total_bytes": total,
        "flat_total_bytes": flat_total,
        "reduction_vs_flat": (flat_total / total) if total else None,
        "supersteps": executed,
        "truncated": bool(av[-1] != 0) and executed > levels - 1,
        "delta_supersteps": schedule.count("delta"),
        "bitmap_supersteps": schedule.count("bitmap"),
        "flat_supersteps": schedule.count("flat"),
    }
    return out


# ---------------------------------------------------------------------------
# 2D grid: per-axis arms (ISSUE 17)
#
# On the r x c mesh a superstep has TWO wire moves, armed independently:
#
#   column axis (frontier broadcast) — each cell all-gathers its owned
#     frontier words along the mesh row's c cells, producing the row
#     stripe R_i's frontier [c*nw words].  Semantically the 1D exchange
#     at group size c, so the three 1D arms are reused verbatim (with
#     the delta density vote widened to both axes); the own-word tables
#     passed in are the mesh row's c rows of the replicated [n, kw]
#     table, so the sieve carries over unchanged.
#   row axis (candidate min-reduce) — each mesh column min-reduces
#     per-destination ORIGINAL-source-id candidates (u32, 0xFFFFFFFF =
#     "no candidate") over its r cells, settling the column stripe C_j.
#     Candidates are 32-bit per VERTEX (not packed bits), so the dense
#     reduce is 32x a frontier word and arming matters even more:
#       flat    — lax.pmin over the whole r*block candidate vector
#       bitmap  — compact pmin: candidates regrouped through the mesh
#                 column's own-word tables first, so structurally-padded
#                 words never ship (the row-axis analogue of the sieved
#                 bitmap; same payload shape on every cell of the column,
#                 which is what makes the elementwise pmin correct)
#       delta   — budgeted (index, value) list of live candidates,
#                 all-gather + scatter-min, with the compact-pmin
#                 fallback under ONE both-axes replicated density vote.
#                 Unlike the 1D delta, FORCED delta keeps the fallback:
#                 a static budget covering the dense worst case would be
#                 r*block entries (the flat arm), so the forced budget is
#                 r*kw entries and dense supersteps spill to compact pmin
#                 (docs/ARCHITECTURE.md §25 records the deviation).
#
# Byte accounting keeps the 1D convention (each participant's payload
# counted once: 4 * group_size * payload_words per group) and scales by
# the number of groups (r mesh rows for the column axis, c mesh columns
# for the row axis) so the accumulators record MACHINE totals — divide by
# r*c for per-chip wire.  A size-1 axis is the identity: zero bytes, arm
# code 0 ("none" in the schedule), which is exactly how 1x8 degenerates
# to the 1D semantics.
# ---------------------------------------------------------------------------


def grid_row_budget(cfg: ExchangeConfig, r: int, kw: int) -> int:
    """Static entry budget for the row-axis candidate list: ``r*kw``
    entries forced-delta (one live candidate per real owned word of the
    column stripe — past that density the compact arm is the cheaper
    ship anyway), ``ceil(r*kw / div)`` for auto."""
    if cfg.mode == "delta":
        return int(r * kw)
    return max(1, math.ceil(int(r) * int(kw) / int(cfg.budget_div)))


def make_grid_col_exchange(cfg: ExchangeConfig, kw: int, nw: int,
                           r: int, c: int,
                           col_axis: str = "col", row_axis: str = "row"):
    """Column-axis frontier broadcast closure: ``(send_words u32[nw],
    own_local i32[kw], own_row i32[c, kw]) -> (stripe_words u32[c*nw],
    machine_bytes i32, arm_code i32)``.  ``own_row`` is the mesh row's
    slice of the replicated own-word table (rows ``[i*c, i*c+c)``)."""
    if c == 1:
        return lambda w, ol, orow: (w, jnp.int32(0), jnp.int32(0))
    scale = jnp.int32(r)
    if cfg.mode == "flat":
        def col_flat(w, ol, orow):
            fw, nb, arm = exchange_flat(w, c, col_axis)
            return fw, nb * scale, arm
        return col_flat
    if cfg.mode == "bitmap":
        def col_bitmap(w, ol, orow):
            fw, nb, arm = exchange_bitmap(w, ol, orow, nw, col_axis)
            return fw, nb * scale, arm
        return col_bitmap
    budget = cfg.delta_budget(kw)

    def col_delta(w, ol, orow):
        fw, nb, arm = exchange_delta(
            w, ol, orow, nw, budget, col_axis,
            fits_axes=(row_axis, col_axis),
        )
        return fw, nb * scale, arm
    return col_delta


def make_grid_row_reduce(cfg: ExchangeConfig, kw: int, nw: int,
                         r: int, c: int,
                         row_axis: str = "row", col_axis: str = "col"):
    """Row-axis candidate min-reduce closure: ``(cand u32[r*block],
    own_cj i32[r, kw]) -> (candg u32[r*block], machine_bytes i32,
    arm_code i32)``.  ``cand`` holds min-ORIGINAL-source-id candidates
    for the column stripe C_j (stripe position i2 covers block
    ``i2*c + j`` at ``[i2*block, (i2+1)*block)``), already sieved by the
    caller's reached-carry; ``own_cj`` is the column stripe's own-word
    tables (``own_table[i2*c + j]`` stacked over i2 — identical on every
    cell of the mesh column).  ``candg`` is the replicated min."""
    block = nw * 32
    rb = r * block
    sent = jnp.uint32(0xFFFFFFFF)
    if r == 1:
        return lambda cand, own_cj: (cand, jnp.int32(0), jnp.int32(0))
    groups = jnp.int32(c)

    def row_flat(cand, own_cj):
        candg = jax.lax.pmin(cand, row_axis)
        return candg, jnp.int32(4 * r * rb) * groups, jnp.int32(EX_FLAT)

    def _compact_pmin(cand, own_cj):
        comp = jnp.take_along_axis(
            cand.reshape(r, nw, 32), own_cj[:, :, None], axis=1
        )  # [r, kw, 32] — the column stripe's REAL words only
        comp = jax.lax.pmin(comp, row_axis)
        out3 = jnp.full((r, nw, 32), sent, jnp.uint32)
        out3 = out3.at[jnp.arange(r)[:, None], own_cj, :].set(comp)
        return out3.reshape(rb)

    def row_bitmap(cand, own_cj):
        candg = _compact_pmin(cand, own_cj)
        return candg, jnp.int32(4 * r * (r * kw * 32)) * groups, \
            jnp.int32(EX_BITMAP)

    if cfg.mode == "flat":
        return row_flat
    if cfg.mode == "bitmap":
        return row_bitmap
    budget = grid_row_budget(cfg, r, kw)

    def row_delta(cand, own_cj):
        live = cand != sent
        count = live.sum(dtype=jnp.int32)
        fits = jax.lax.pmax(
            count, (row_axis, col_axis)
        ) <= jnp.int32(budget)

        def lst(cand):
            idx = jnp.sort(
                jnp.where(live, jnp.arange(rb, dtype=jnp.int32),
                          jnp.int32(rb))
            )[:budget]
            vals = jnp.where(idx < rb, cand[jnp.clip(idx, 0, rb - 1)], sent)
            payload = jnp.concatenate([idx.astype(jnp.uint32), vals])
            gath = jax.lax.all_gather(payload, row_axis)  # [r, 2B]
            gi = gath[:, :budget].astype(jnp.int32)
            gv = gath[:, budget:]
            flat = jnp.where(gi < rb, gi, jnp.int32(rb)).reshape(-1)
            candg = jnp.full((rb,), sent, jnp.uint32).at[flat].min(
                gv.reshape(-1), mode="drop"
            )
            return candg, jnp.int32(4 * r * 2 * budget) * groups, \
                jnp.int32(EX_DELTA)

        def fall(cand):
            # pmin leaves the words invariant over row; all_gather in the
            # list arm does not, and cond's branches must agree.
            candg, nb, arm = row_bitmap(cand, own_cj)
            return pcast_varying(candg, (row_axis,)), nb, arm

        return jax.lax.cond(fits, lst, fall, cand)
    return row_delta


def grid_exchange_report(col_bytes, col_arms, row_bytes, row_arms,
                         cfg: ExchangeConfig, kw: int, nw: int,
                         r: int, c: int,
                         num_levels: int | None = None) -> dict:
    """JSON-ready ``details.exchange`` for a grid run: the per-level
    byte/arm curves for EACH axis plus the combined totals, against the
    same 1D-flat baseline the 1D report uses (``n * nw * 4`` bytes per
    executed superstep at ``n = r*c`` — what the 1D uncompressed
    exchange ships for the SAME search on the same shard layout).  The
    per-axis column names (``col_bytes``/``row_bytes``) are the contract
    ``tools/ledger_compare.py --exact`` diffs."""
    import numpy as np

    n = r * c
    bvc = np.asarray(col_bytes, dtype=np.int64)
    avc = np.asarray(col_arms, dtype=np.int64)
    bvr = np.asarray(row_bytes, dtype=np.int64)
    avr = np.asarray(row_arms, dtype=np.int64)
    nz = np.flatnonzero(avc | avr)
    levels = int(nz[-1]) + 1 if nz.size else 0
    executed = (
        int(num_levels) if num_levels is not None
        else (levels - 1 if levels else 0)
    )
    if num_levels is not None and executed + 1 < len(avc):
        # Size-1-axis runs leave one accumulator all-zero; trust the
        # loop-exit count for the per-level window in that case too.
        levels = max(levels, min(executed + 1, len(avc)))
    col_sched = [EX_NAMES.get(int(x), "none") for x in avc[1:levels]]
    row_sched = [EX_NAMES.get(int(x), "none") for x in avr[1:levels]]
    col_total = int(bvc.sum())
    row_total = int(bvr.sum())
    total = col_total + row_total
    flat_total = int(executed * n * nw * 4)
    per_level = [int(a + b) for a, b in zip(bvc[1:levels], bvr[1:levels])]
    return {
        "arm": cfg.mode,
        "mesh": f"{r}x{c}",
        "col_budget_words": int(cfg.delta_budget(kw)),
        "row_budget_entries": int(grid_row_budget(cfg, r, kw)),
        "col_bytes": [int(x) for x in bvc[1:levels]],
        "row_bytes": [int(x) for x in bvr[1:levels]],
        "bytes_per_level": per_level,
        "col_schedule": col_sched,
        "row_schedule": row_sched,
        # index i = the superstep that settled level i+1 (1D convention)
        "schedule": [
            f"{a}+{b}" for a, b in zip(col_sched, row_sched)
        ],
        "col_total_bytes": col_total,
        "row_total_bytes": row_total,
        "total_bytes": total,
        "flat_total_bytes": flat_total,
        "reduction_vs_flat": (flat_total / total) if total else None,
        "per_chip_bytes": (total / n) if n else 0.0,
        "supersteps": executed,
        "truncated": bool((avc[-1] | avr[-1]) != 0) and executed > levels - 1,
        "axes": {
            "col": {"size": c, "groups": r, "total_bytes": col_total},
            "row": {"size": r, "groups": c, "total_bytes": row_total},
        },
    }
