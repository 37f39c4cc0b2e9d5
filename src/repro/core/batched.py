"""Batched (vectorized) engine — the TPU-shaped semantics.

Processes B stream elements per step:

  1. hash all B keys (fused k-way hashing — `kernels/hashmix` on TPU),
  2. probe the batch-entry snapshot of the filters,
  3. *exact* intra-batch first-occurrence detection (sort by key): a later
     equal key inside the batch is always reported duplicate,
  4. vectorized per-variant insert/delete decisions using per-element stream
     positions ``i_t = position + t``,
  5. one scatter pass: deletions from the snapshot first, then insertions
     (insertions win — conservative w.r.t. false negatives),
  6. *exact incremental* load update from the scatter pre-values — an
     O(B log B) event sort instead of an O(s) popcount over the filter
     (DESIGN.md §3.1; ``cfg.debug_exact_load`` restores the full popcount).

Divergence from the sequential oracle is bounded (deletions can't wipe
same-batch insertions; RSBF may report a within-batch repeat of a *rejected*
first occurrence as duplicate) and is measured in
``tests/test_core_variants.py::test_batched_tracks_oracle`` (DESIGN.md §2).

``valid`` masks let ragged stream tails ride through fixed-shape jit steps as
no-ops.

Every variant is described by a ``SketchSpec`` (``core.sketch``, DESIGN.md
§3.8): probe op, decision fn, event-delta op, load-delta op, and the state's
plane count d. ``make_batched_step`` generates the jnp step from the spec
(``make_templated_step`` below — one factory for both the bitset and counter
families), and ``repro.kernels.fused_template.make_fused_step`` generates
the Pallas step as THIS step with its filter-sized update handed to one
kernel (the ``apply`` hook), so the two backends are bit-identical by
construction (DESIGN.md §3.4/§3.6/§3.8).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import DedupConfig
from .hashing import derive_seeds, hash_positions, uniform_positions
from .packed import (clamped_run_counts, count_planes_from_sorted,
                     planes_nonzero, planes_saturating_add,
                     planes_saturating_sub, planes_set_value, popcount,
                     probe_cell_values, probe_packed, run_heads,
                     run_heads_1d, split_pos, update_sorted_positions)
from .state import FilterState, WindowRing


class BatchResult(NamedTuple):
    dup: jnp.ndarray        # (B,) bool — reported duplicate
    inserted: jnp.ndarray   # (B,) bool — element was inserted into the filters


class BatchRandomness(NamedTuple):
    """Pre-drawn randomness for one batched step. Unused fields are zeros of
    the right shape so both backends consume an identical pytree."""
    del_pos: jnp.ndarray    # (B, k) int32 — candidate deletion positions
    u_bern: jnp.ndarray     # (B,) f32    — RSBF phase-2 insertion bernoulli
    u_aux: jnp.ndarray      # (B, k) f32  — RLBSBF per-filter deletion uniforms
    which: jnp.ndarray      # (B,) int32  — BSBFSD's single chosen filter


BatchedStep = Callable[[FilterState, jnp.ndarray, jnp.ndarray],
                       Tuple[FilterState, BatchResult]]


def intra_batch_seen(keys: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """(B,) bool: True where an equal *valid* key occurs earlier in the batch.

    Value-free sort + rank join: XLA lowers a single-operand ``sort`` to a
    fast vectorized kernel, while stable *argsort* (a two-operand comparator
    sort) is several times slower on every backend — so instead of carrying
    lane indices through the sort, each lane finds its key's rank with a
    binary search and the earliest lane per key is elected with a B-sized
    scatter-min (DESIGN.md §3.1). Invalid lanes share a sentinel key.
    """
    b = keys.shape[0]
    sk = jnp.where(valid, keys, jnp.uint32(0xFFFFFFFF))
    sorted_k = jnp.sort(sk)
    rank = jnp.searchsorted(sorted_k, sk, side="left").astype(jnp.int32)
    lane = jnp.arange(b, dtype=jnp.int32)
    winner = jnp.full((b,), b, jnp.int32).at[rank].min(lane)
    return (winner[rank] != lane) & valid


def draw_randomness(cfg: DedupConfig, rng: jax.Array, b: int
                    ) -> Tuple[jax.Array, BatchRandomness]:
    """Split the state rng and draw every random input of one batched step.

    The split/draw order is frozen (it is part of the engine's determinism
    contract — tests pin dup reports at fixed seed across refactors): one
    4-way split, del_pos from r_del, then the variant's extra draws from the
    same keys the original inline code used.
    """
    k, s = cfg.k, cfg.s
    rng, r_ins, r_del, r_aux = jax.random.split(rng, 4)
    del_pos = uniform_positions(r_del, (b, k), s)
    u_bern = (jax.random.uniform(r_ins, (b,))
              if cfg.variant == "rsbf" else jnp.zeros((b,), jnp.float32))
    u_aux = (jax.random.uniform(r_aux, (b, k))
             if cfg.variant == "rlbsbf" else jnp.zeros((b, k), jnp.float32))
    which = (jax.random.randint(r_aux, (b,), 0, k, dtype=jnp.int32)
             if cfg.variant == "bsbfsd" else jnp.zeros((b,), jnp.int32))
    return rng, BatchRandomness(del_pos, u_bern, u_aux, which)


def make_decision_fn(cfg: DedupConfig):
    """Pure per-variant decision logic of the bitset family — one function
    for both backends (the fused step runs it in XLA before its kernel).

    decide(vals, valid, seen, i_t, load, rnd) ->
        (dup (B,) bool, insert (B,) bool, del_mask (B, k) bool)
    """
    s, k = cfg.s, cfg.k

    def decide(vals, valid, seen, i_t, load, rnd: BatchRandomness):
        rows = jax.lax.iota(jnp.int32, k)
        b = valid.shape[0]
        filter_dup = jnp.all(vals == 1, axis=1)
        dup = (filter_dup | seen) & valid
        distinct = valid & ~dup
        if cfg.variant == "rsbf":
            p_ins = jnp.float32(s) / i_t.astype(jnp.float32)
            ph1 = i_t <= s
            ph3 = p_ins <= cfg.p_star
            bern = rnd.u_bern < p_ins
            insert = jnp.where(
                ph1, valid,
                jnp.where(ph3, distinct, distinct & bern))
            ph2_del = ((~ph1) & (~ph3) & insert)[:, None]
            ph3_del = (ph3 & insert)[:, None] & (vals == 0)
            del_mask = jnp.where(ph3[:, None], ph3_del,
                                 jnp.broadcast_to(ph2_del, (b, k)))
        elif cfg.variant == "bsbf":
            insert = distinct
            del_mask = jnp.broadcast_to(insert[:, None], (b, k))
        elif cfg.variant == "bsbfsd":
            insert = distinct
            del_mask = insert[:, None] & (rnd.which[:, None] == rows[None, :])
        elif cfg.variant == "rlbsbf":
            insert = distinct
            p_del = load.astype(jnp.float32)[None, :] / jnp.float32(s)
            del_mask = insert[:, None] & (rnd.u_aux < p_del)
        else:
            raise ValueError(cfg.variant)
        return dup, insert, del_mask

    return decide


def sorted_enabled_positions(pos: jnp.ndarray, mask: jnp.ndarray,
                             sentinel: int) -> jnp.ndarray:
    """(B, k) positions + enable mask -> (k, B) uint32 ascending per row;
    disabled lanes carry ``sentinel`` (> any real position) and sort to the
    end. uint32, so the sentinel 32·W = 2^31 of the paper's 512 MB filter
    (s = 2^31) fits; real positions are < 2^31 and sort as they would in
    int32.

    A *value-free* single-operand sort — everything downstream (delta words,
    pre-values, first-occurrence flags) is recomputed from the sorted
    positions instead of permuted alongside them, because multi-operand
    sorts hit XLA's slow comparator path (DESIGN.md §3.1/§3.2).
    """
    return jnp.sort(jnp.where(mask, pos.astype(jnp.uint32),
                              jnp.uint32(sentinel)).T, axis=-1)


def load_delta_from_sorted(spi: jnp.ndarray, pre_i: jnp.ndarray,
                           spd: jnp.ndarray, pre_d: jnp.ndarray,
                           post_d: jnp.ndarray, s: int) -> jnp.ndarray:
    """Exact per-row load delta of the batched update R = (A & ~D) | I.

    spi / spd: (k, B) *sorted* uint32 insert / delete positions (sentinel
    >= s for disabled lanes); pre_*: the corresponding PRE-update bit values {0,1};
    post_d: the POST-update bits at the delete positions. Intra-batch
    duplicate positions count once (run heads of the sorted arrays). A bit
    both deleted and inserted nets the insert — since deletes apply before
    inserts, a deleted position ends at R[p] = I[p], so ``post_d`` IS the
    "was it re-inserted" flag: one O(B) gather replaces a sorted-set join.
    O(B log B) total, no O(s) reduce over the filter (DESIGN.md §3.1).
    """
    gained = jnp.sum(
        jnp.where(run_heads(spi) & (spi < jnp.uint32(s)),
                  1 - pre_i.astype(jnp.int32), 0),
        axis=-1)
    lost = jnp.sum(
        jnp.where(run_heads(spd) & (spd < jnp.uint32(s)) & (post_d == 0),
                  pre_d.astype(jnp.int32), 0), axis=-1)
    return (gained - lost).astype(jnp.int32)


class SbfBatchDeltas(NamedTuple):
    """One SBF batch's filter-touching events, reduced to word deltas
    (DESIGN.md §3.6). Shared by the jnp plane step and the fused Pallas
    counter kernel — both backends apply the SAME deltas, so they are
    bit-identical by construction. The sorted event arrays ride along for
    the jnp step's load accounting (the kernel ignores them — in one jitted
    program the unused sorts are dead-code-eliminated)."""
    count_planes: jnp.ndarray   # (d, W) uint32 — decrement counts per cell,
                                #   clamped to Max, as bit-planes
    set_delta: jnp.ndarray      # (W,) uint32 — OR-union of set-to-Max cells
    dec_sorted: jnp.ndarray     # (B·P,) int32 — sorted decrement cells
                                #   (sentinel 32W for invalid lanes)
    dec_head: jnp.ndarray       # (B·P,) bool — first event of each cell
    set_sorted: jnp.ndarray     # (B·k,) int32 — sorted set-to-Max cells
    set_head: jnp.ndarray       # (B·k,) bool — first event of each cell


def draw_sbf_randomness(cfg: DedupConfig, rng: jax.Array, b: int):
    """SBF's per-batch randomness: the decrement-run start cells. The
    split/draw order is frozen and identical to the dense8 branch (and, at
    b == 1, to the sequential oracle) — part of the determinism contract."""
    rng, r = jax.random.split(rng)
    start = uniform_positions(r, (b,), cfg.s)
    return rng, start


def sbf_event_deltas(cfg: DedupConfig, pos: jnp.ndarray, start: jnp.ndarray,
                     valid: jnp.ndarray) -> SbfBatchDeltas:
    """Batch events -> word deltas through the sorted-position machinery.

    Decrement runs: each valid element decrements the P contiguous cells
    from its random start (wrapping) by 1, saturating at 0 — so a cell's
    decrement is the NUMBER of runs covering it. The B·P run cells are
    sorted (one value-free sort, §3.1 discipline); a cell's multiplicity is
    read off the sorted array with Max-1 shifted equality compares (clamping
    to Max is lossless under saturation since value <= Max); each cell's
    HEAD event scatter-ADDs its count once, packed as a d-bit field
    (``counts_to_planes`` layout) — heads are unique per cell, so fields
    never collide and one scatter entry per event replaces both the
    segmented scan and any read-modify-write. Set-to-Max cells build their
    OR-union delta the same way: head-only single-bit masks are disjoint
    within a word, so scatter-add IS the OR (§3.2/§3.6). O(B·P log(B·P))
    event work, no O(s) buffer anywhere.
    """
    s, W = cfg.s, cfg.s_words
    d, cmax, p_run = cfg.n_planes, cfg.sbf_max, cfg.sbf_p_effective
    sentinel = 32 * W
    run = (start[:, None] + jnp.arange(p_run, dtype=jnp.int32)) % s  # (B, P)
    spd = jnp.sort(jnp.where(valid[:, None], run, sentinel).reshape(-1))
    dec_head, cnt = clamped_run_counts(spd, cmax)
    count_planes = count_planes_from_sorted(spd, dec_head, cnt, d, W)  # (d, W)
    # set-to-Max OR delta: head-only masks are disjoint bits per word
    sps = jnp.sort(jnp.where(valid[:, None], pos, sentinel).reshape(-1))
    set_head = run_heads_1d(sps)
    smask = jnp.where(set_head,
                      jnp.uint32(1) << (sps & 31).astype(jnp.uint32),
                      jnp.uint32(0))
    set_delta = jnp.zeros((W,), jnp.uint32).at[sps >> 5].add(
        smask, mode="drop")                                        # (W,)
    return SbfBatchDeltas(count_planes, set_delta, spd, dec_head, sps,
                          set_head)


def sbf_planes_3d(bits: jnp.ndarray) -> jnp.ndarray:
    """Normalize an SBF plane state to (d, 1, W) — Max == 1 squeezes d."""
    return bits if bits.ndim == 3 else bits[None]


def make_sbf_planes_step(cfg: DedupConfig) -> BatchedStep:
    """SBF on the plane layout (DESIGN.md §3.6) — the sketch template's
    counter step under the "sbf" spec, kept as a named factory for
    back-compat. Bit-identical to the dense8 SBF branch (same probes, same
    rng draws, same snapshot semantics, same cell values and load)."""
    from .sketch import get_spec
    return make_counter_planes_step(cfg, get_spec("sbf"))


class CountBatchDeltas(NamedTuple):
    """One batch's insert/increment events, reduced to word deltas (DESIGN.md
    §3.7/§3.8). Shared by the jnp plane step and the fused Pallas kernel —
    both backends apply (and, for swbf, ring-store) the SAME deltas, so they
    are bit-identical by construction."""
    count_planes: jnp.ndarray   # (d, W) uint32 — per-cell event
                                #   multiplicities clamped to 2^d - 1,
                                #   as bit-planes (swbf: the ring payload)
    ins_sorted: jnp.ndarray     # (E,) int32 — sorted insert cells, sentinel
                                #   32·W padded to the event width
    ins_head: jnp.ndarray       # (E,) bool — first event of each cell


def count_event_deltas(cfg: DedupConfig, pos: jnp.ndarray, valid: jnp.ndarray,
                       width: int) -> CountBatchDeltas:
    """A batch's B·k insert positions -> clamped count planes + the sorted
    event list, through the same one-sort machinery as the SBF deltas: a
    cell's increment is its event multiplicity clamped to the counter cap
    2^d - 1 (clamping is consistent — swbf's ring stores and later subtracts
    the SAME clamped planes, and the host oracle replicates it). ``width``
    pads the sorted list with sentinels — B·k for the counting sketches, the
    ring's event capacity for swbf, so ragged batches (and the sharded
    dispatch width) share one slot shape."""
    W, d = cfg.s_words, cfg.n_planes
    cmax = (1 << d) - 1
    sentinel = 32 * W
    flat = jnp.where(valid[:, None], pos, sentinel).reshape(-1)
    if width < flat.shape[0]:
        raise ValueError(
            f"{cfg.variant} step saw {flat.shape[0]} events but the event "
            f"width is {width} — init the state with event_capacity >= the "
            f"step's element count (DESIGN §3.7)")
    if width > flat.shape[0]:
        flat = jnp.concatenate(
            [flat, jnp.full((width - flat.shape[0],), sentinel, flat.dtype)])
    sp = jnp.sort(flat)
    head, cnt = clamped_run_counts(sp, cmax)
    count_planes = count_planes_from_sorted(sp, head, cnt, d, W)   # (d, W)
    return CountBatchDeltas(count_planes, sp, head)


def ring_expire_planes(cfg: DedupConfig, ring: WindowRing):
    """Re-expand the expiring slot's sorted event list into its (d, W)
    packed count planes — the subtrahend for ``planes_saturating_sub``.

    Deterministic re-expansion of the SAME list the arrival batch built its
    increment planes from, so expiry removes exactly what arrival added
    (modulo the cells' saturation, which the host oracle replicates). One
    event-sized scatter; the stored list is already sorted, so no sort.
    Returns (events, heads, count_planes) — the events/heads feed the §3.1
    load accounting."""
    ev = jax.lax.dynamic_index_in_dim(ring.events, ring.slot, 0,
                                      keepdims=False)             # (E,)
    head, cnt = clamped_run_counts(ev, (1 << cfg.n_planes) - 1)
    planes = count_planes_from_sorted(ev, head, cnt, cfg.n_planes,
                                      cfg.s_words)                # (d, W)
    return ev, head, planes


def ring_push(ring: WindowRing, ev: CountBatchDeltas, window: int
              ) -> WindowRing:
    """Overwrite the expired slot with the arriving batch's event list and
    advance. Identical jnp code on both backends — the ring is engine
    state, not kernel state (the kernel only consumes the expiring slot's
    re-expanded planes)."""
    events = jax.lax.dynamic_update_index_in_dim(
        ring.events, ev.ins_sorted, ring.slot, 0)
    return WindowRing(events, (ring.slot + 1) % window)


def make_swbf_planes_step(cfg: DedupConfig) -> BatchedStep:
    """Sliding-window counting-Bloom dedup on the plane layout (DESIGN.md
    §3.7) — the sketch template's counter step under the "swbf" spec, kept
    as a named factory for back-compat: snapshot probe (duplicate iff all k
    probed cells nonzero OR an equal key occurred earlier in the batch),
    borrow-chain expiry of the oldest slot, carry-chain increment of the
    arriving batch, exact incremental load (§3.1 discipline), rng untouched.
    """
    from .sketch import get_spec
    return make_counter_planes_step(cfg, get_spec("swbf"))


class TenantStepParams(NamedTuple):
    """Per-tenant numeric knobs broadcast into ONE fleet launch (DESIGN
    §4.6): scalar int32 leaves inside a step (one tenant's row), stacked
    (T,) arrays at the fleet level — ``jax.vmap`` maps the tenant axis.
    Only value-like knobs ride here; anything shape-affecting (k, d, s, W,
    ring length) stays fleet-wide static so every tenant traces the same
    program. ``max_value`` must share ``cfg.sbf_max``'s bit_length (d is
    static); ``window`` must be <= the fleet ring length ``cfg.window``."""
    max_value: jnp.ndarray      # () int32 — sbf set-to-Max counter ceiling
    threshold: jnp.ndarray      # () int32 — cms/hh verdict threshold
    window: jnp.ndarray         # () int32 — swbf effective window (batches)


class CounterStepDeltas(NamedTuple):
    """A counter-family batch reduced to the plane algebra's operands
    (DESIGN.md §3.8). Built per-spec (``core.sketch``) and consumed
    identically by the jnp step and the fused Pallas kernel wrapper — the
    plane deltas become kernel operands, the sorted event lists feed the
    §3.1 load accounting, and the optional ring payload is pushed by the
    engine-side (non-kernel) code. ``None`` marks an op the sketch lacks.
    Application order is fixed: subtract, then set/add (insertions win)."""
    sub_planes: Optional[jnp.ndarray]   # (d, W) u32 decrement planes
    sub_events: Optional[jnp.ndarray]   # (E,) i32 sorted decrement cells
    sub_heads: Optional[jnp.ndarray]    # (E,) bool first event per cell
    add_planes: Optional[jnp.ndarray]   # (d, W) u32 increment planes
    set_delta: Optional[jnp.ndarray]    # (W,) u32 set-to-Max OR mask
    ins_events: jnp.ndarray             # (E',) i32 sorted insert cells
    ins_heads: jnp.ndarray              # (E',) bool first event per cell
    ring_payload: Optional[CountBatchDeltas]  # swbf: this batch's ring slot


def make_counter_planes_step(cfg: DedupConfig, spec,
                             params_aware: bool = False,
                             apply=None) -> BatchedStep:
    """The counter-family step generator (DESIGN.md §3.8): one jnp ingest
    step over the (d, W) bit-plane algebra, specialized by a ``SketchSpec``
    — probe op (nonzero bit vs d-bit cell value), decision fn, event-delta
    builder (decrement/set/add planes + sorted event lists), and the §3.1
    exact incremental nonzero-cell load shared by every sketch. sbf, swbf,
    cms and hh are all THIS function under different specs; the fused
    Pallas twin is generated from the same spec by
    ``kernels.fused_template.make_fused_step``.

    ``params_aware=True`` (the fleet path, DESIGN §4.6) appends a
    ``TenantStepParams`` argument: step(state, keys, valid, tp). The traced
    per-tenant scalars replace the static config values at the three
    value-like seams — the cms/hh verdict threshold, the sbf set-to-Max
    ceiling, and the swbf ring-slot advance modulus — leaving every shape
    and every rng draw untouched, so one trace serves all tenants under
    ``jax.vmap``.

    ``apply`` replaces the filter-sized update: ``apply(planes (d, W),
    load, ev, cmax) -> (new planes, new load)``. The fused Pallas step
    (``kernels.fused_template``) passes its kernel here, so probe, decide,
    randomness, events and ring are this very code on both backends."""
    cfg = cfg.validate().check_int32_positions()
    seeds = derive_seeds(cfg.seed, cfg.k, channel=0)
    bseeds = (derive_seeds(cfg.seed, cfg.k, channel=1)
              if cfg.block_bits else None)
    s = cfg.s
    squeeze = cfg.n_planes == 1
    decide = spec.make_decide(cfg)
    events_fn = spec.make_events(cfg)

    def step(state: FilterState, keys: jnp.ndarray, valid: jnp.ndarray,
             tp: Optional[TenantStepParams] = None):
        b = keys.shape[0]
        planes = sbf_planes_3d(state.bits)[:, 0, :]               # (d, W)
        pos = hash_positions(keys, seeds, s, cfg.block_bits, bseeds)   # (B, k)
        nzw = planes_nonzero(planes)                              # (W,)
        if spec.probe == "value":
            vals = probe_cell_values(planes, pos)                 # (B, k) i32
        else:
            w_idx, mask = split_pos(pos)
            vals = (nzw[w_idx] & mask) != 0                       # (B, k) bool
        seen = intra_batch_seen(keys, valid) if spec.uses_seen else None
        if params_aware and spec.thresholded:
            dup = decide(vals, valid, seen, t=tp.threshold)
        else:
            dup = decide(vals, valid, seen)
        if spec.draw is not None:
            rng, rnd = spec.draw(cfg, state.rng, b)
        else:
            rng, rnd = state.rng, None
        ev = events_fn(state, pos, valid, rnd)
        # set-to-Max writes the sketch's counter ceiling (sbf_max), which
        # may sit below the plane capacity 2^d - 1
        cmax = tp.max_value if params_aware else cfg.sbf_max
        if apply is not None:
            new, load = apply(planes, state.load, ev, cmax)
        else:
            new, load = _counter_update(cfg, planes, state.load, ev, cmax,
                                        nzw)
        bits = new[:, None, :] if not squeeze else new
        ring = state.ring
        if ev.ring_payload is not None:
            window = tp.window if params_aware else cfg.window
            ring = ring_push(ring, ev.ring_payload, window)
        n_valid = valid.sum(dtype=jnp.int32)
        new_state = FilterState(bits, state.position + n_valid, load, rng,
                                ring)
        return new_state, BatchResult(dup=dup, inserted=valid)

    return step


def _counter_update(cfg: DedupConfig, planes, load, ev, cmax, nzw):
    """The jnp counter-family update: subtract, then set/add (insertions
    win), and the §3.1 exact incremental nonzero-cell load."""
    W = cfg.s_words
    new = planes
    if ev.sub_planes is not None:
        new = planes_saturating_sub(new, ev.sub_planes)
    if ev.set_delta is not None:
        new = planes_set_value(new, ev.set_delta, cmax)
    if ev.add_planes is not None:
        new = planes_saturating_add(new, ev.add_planes)
    if cfg.debug_exact_load:
        load = popcount(planes_nonzero(new)[None])
    else:
        # exact incremental load (nonzero-cell count, §3.1):
        #   gained — insert/set cells whose PRE value was zero (their
        #            head event leaves them nonzero);
        #   lost   — decremented cells that were nonzero and whose POST
        #            nonzero bit is clear (decayed to zero, not
        #            refreshed — inserts apply after decrements, so the
        #            post bit IS the "was it refreshed" flag).
        # Each cell counts once (run heads); batch-sized gathers only.
        new_nz = planes_nonzero(new)
        sentinel = 32 * W

        def nz_bit(words, sp):
            got = words[jnp.minimum(sp >> 5, W - 1)]
            return (got >> (sp & 31).astype(jnp.uint32)) & jnp.uint32(1)

        gained = jnp.sum(ev.ins_heads & (ev.ins_events < sentinel)
                         & (nz_bit(nzw, ev.ins_events) == 0),
                         dtype=jnp.int32)
        if ev.sub_events is None:
            lost = jnp.int32(0)
        else:
            lost = jnp.sum(ev.sub_heads & (ev.sub_events < sentinel)
                           & (nz_bit(nzw, ev.sub_events) == 1)
                           & (nz_bit(new_nz, ev.sub_events) == 0),
                           dtype=jnp.int32)
        load = load + gained - lost
    return new, load


def _make_sbf_dense8_step(cfg: DedupConfig) -> BatchedStep:
    """Dense uint8 SBF reference branch — deliberately NOT spec-driven: it
    is the cross-check the plane steps are tested bit-identical against, so
    it keeps its own naive scatter/recount formulation (DESIGN.md §3.6)."""
    cfg.check_int32_positions()
    seeds = derive_seeds(cfg.seed, cfg.k, channel=0)
    bseeds = (derive_seeds(cfg.seed, cfg.k, channel=1)
              if cfg.block_bits else None)
    s = cfg.s
    p_run, cmax = cfg.sbf_p_effective, cfg.sbf_max

    def step(state: FilterState, keys: jnp.ndarray, valid: jnp.ndarray):
        b = keys.shape[0]
        pos = hash_positions(keys, seeds, s, cfg.block_bits, bseeds)   # (B, k)
        vals = state.bits[0, pos]                             # (B, k)
        dup = jnp.all(vals > 0, axis=1) & valid
        rng, start = draw_sbf_randomness(cfg, state.rng, b)
        run = (start[:, None] + jnp.arange(p_run, dtype=jnp.int32)) % s
        run = jnp.where(valid[:, None], run, s)               # drop pads
        dec = jnp.zeros((s,), jnp.int32).at[run.reshape(-1)].add(
            1, mode="drop")
        cells = jnp.maximum(state.bits[0].astype(jnp.int32) - dec, 0)
        bits = cells.astype(jnp.uint8)[None, :]
        set_pos = jnp.where(valid[:, None], pos, s)
        bits = bits.at[0, set_pos.reshape(-1)].set(jnp.uint8(cmax),
                                                   mode="drop")
        # counters decay by runs of P — no cheap per-bit delta exists, so
        # the SBF *baseline* keeps the O(s) recount (DESIGN.md §3.1)
        load = jnp.array([(bits[0] > 0).sum(dtype=jnp.int32)])
        n_valid = valid.sum(dtype=jnp.int32)
        new = FilterState(bits, state.position + n_valid, load, rng)
        return new, BatchResult(dup=dup, inserted=valid)

    return step


def make_bitset_step(cfg: DedupConfig, spec, apply=None) -> BatchedStep:
    """The bitset-family step generator (DESIGN.md §3.1/§3.8): one jnp
    ingest step over the 1-bit R = (A & ~D) | I algebra, specialized by a
    ``SketchSpec`` — the spec supplies the decision fn and the randomness
    draw; probe/scatter/load are the family-shared machinery. rsbf, bsbf,
    bsbfsd and rlbsbf are all THIS function under different specs.

    ``apply`` (plane layout only) replaces the touched-word update:
    ``apply(bits (k, W), load, spi, spd) -> (new bits, new load)`` from the
    (k, B) sorted uint32 insert/delete positions. The fused Pallas step
    passes its kernel here, so everything before the update is this very
    code on both backends."""
    cfg = cfg.validate().check_int32_positions()
    seeds = derive_seeds(cfg.seed, cfg.k, channel=0)
    bseeds = (derive_seeds(cfg.seed, cfg.k, channel=1)
              if cfg.block_bits else None)
    s, k = cfg.s, cfg.k
    rows = jnp.arange(k, dtype=jnp.int32)
    decide = spec.make_decide(cfg)
    # sentinel for disabled lanes: beyond the filter AND in word W (so the
    # packed delta scatter drops it) — 32*ceil(s/32), not s, because s's own
    # word can be W-1 when 32 does not divide s
    sentinel = 32 * ((s + 31) // 32)

    def probe(bits, pos):
        if cfg.is_planes:
            return probe_packed(bits, pos)                        # (B, k)
        return bits[rows[None, :], pos]

    def apply_updates(bits, pos, ins_mask, del_pos, del_mask, spi, spd):
        """Deletions from the snapshot, then insertions (insertions win):
        R = (A & ~D) | I -> (new bits, (k,) exact load delta). Packed
        read-modify-writes only the touched words, in place, and counts
        the load from the words it read (DESIGN.md §3.2)."""
        if cfg.is_planes:
            return update_sorted_positions(bits, spi, spd)
        dp = jnp.where(del_mask, del_pos, s)
        new = bits.at[rows[None, :], dp].set(0, mode="drop")
        ip = jnp.where(ins_mask, pos, s)
        new = new.at[rows[None, :], ip].set(1, mode="drop")

        def at(b, sp):
            # row-aligned probe; load_delta_from_sorted masks the sentinels
            return b[rows[:, None], jnp.minimum(sp, s - 1)]

        return new, load_delta_from_sorted(spi, at(bits, spi), spd,
                                           at(bits, spd), at(new, spd), s)

    def recompute_load(bits):
        # debug escape hatch only — O(s) reduce over the whole filter
        if cfg.is_planes:
            return popcount(bits)
        return bits.astype(jnp.int32).sum(axis=1)

    def step(state: FilterState, keys: jnp.ndarray, valid: jnp.ndarray):
        b = keys.shape[0]
        pos = hash_positions(keys, seeds, s, cfg.block_bits, bseeds)                      # (B, k)
        vals = probe(state.bits, pos)                             # (B, k)
        seen = intra_batch_seen(keys, valid)
        i_t = state.position + jnp.arange(b, dtype=jnp.int32)
        rng, rnd = spec.draw(cfg, state.rng, b)
        dup, insert, del_mask = decide(vals, valid, seen, i_t, state.load, rnd)
        ins_mask = jnp.broadcast_to(insert[:, None], (b, k))
        spi = sorted_enabled_positions(pos, ins_mask, sentinel)
        spd = sorted_enabled_positions(rnd.del_pos, del_mask, sentinel)
        if apply is not None:
            bits, load = apply(state.bits, state.load, spi, spd)
            new = FilterState(bits, state.position
                              + valid.sum(dtype=jnp.int32), load, rng)
            return new, BatchResult(dup=dup, inserted=insert)
        bits, delta = apply_updates(state.bits, pos, ins_mask, rnd.del_pos,
                                    del_mask, spi, spd)
        if cfg.debug_exact_load:
            load = recompute_load(bits)
        else:
            load = state.load + delta
        n_valid = valid.sum(dtype=jnp.int32)
        new = FilterState(bits, state.position + n_valid, load, rng)
        return new, BatchResult(dup=dup, inserted=insert)

    return step


def make_templated_step(cfg: DedupConfig, spec=None,
                        params_aware: bool = False) -> BatchedStep:
    """The ONE jnp step factory (DESIGN.md §3.8): resolve the variant's
    ``SketchSpec`` and hand it to the family's generator. Pass ``spec`` to
    run an unregistered/experimental sketch through the same machinery.

    ``params_aware=True`` returns the fleet-signature step
    ``(state, keys, valid, TenantStepParams) -> (state, res)`` (§4.6): the
    counter family threads the traced per-tenant scalars; the bitset family
    — whose decision rule has no value-like config knob — accepts and
    ignores them, keeping the vmapped fleet signature uniform."""
    cfg = cfg.validate()
    if spec is None:
        from .sketch import get_spec
        spec = get_spec(cfg.variant)
    if spec.family == "counter":
        return make_counter_planes_step(cfg, spec, params_aware=params_aware)
    step = make_bitset_step(cfg, spec)
    if not params_aware:
        return step
    return lambda state, keys, valid, tp: step(state, keys, valid)


def make_estimate_fn(cfg: DedupConfig):
    """Serve-path frequency readout for the counting sketches (DESIGN.md
    §3.8): estimate(state, keys) -> (B,) int32 count-min estimates, the MIN
    over the k probed d-bit cell values. Never under-estimates a key's true
    arrival count while every probed counter is below saturation (each
    arrival increments all k of its cells by >= 1, clamped at 2^d - 1);
    over-estimation comes only from hash collisions — the classic CM bound
    eps = e/width at k = ln(1/delta) rows (arXiv:1212.3964 companion
    sketches). Read-only: no state change, no rng consumption."""
    cfg = cfg.validate()
    seeds = derive_seeds(cfg.seed, cfg.k, channel=0)
    bseeds = (derive_seeds(cfg.seed, cfg.k, channel=1)
              if cfg.block_bits else None)
    s = cfg.s

    def estimate(state: FilterState, keys: jnp.ndarray) -> jnp.ndarray:
        planes = sbf_planes_3d(state.bits)[:, 0, :]               # (d, W)
        pos = hash_positions(keys, seeds, s, cfg.block_bits, bseeds)
        return jnp.min(probe_cell_values(planes, pos), axis=1)

    return estimate


def make_batched_step(cfg: DedupConfig) -> BatchedStep:
    """Backend dispatch: the dense8 SBF reference keeps its own branch (it
    is the cross-check, not a template instance); everything else is the
    sketch template — ``fused_template.make_fused_step`` on the Pallas
    backend, ``make_templated_step`` on jnp (DESIGN.md §3.8)."""
    cfg = cfg.validate()
    if cfg.variant == "sbf" and not cfg.is_planes:
        return _make_sbf_dense8_step(cfg)
    if cfg.backend == "pallas":
        from ..kernels.fused_template import make_fused_step
        return make_fused_step(cfg)
    return make_templated_step(cfg)
