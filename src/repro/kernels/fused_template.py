"""Pallas TPU kernel generator: the fused filter sweep of the ingest step,
emitted from a ``SketchSpec`` (DESIGN.md §3.4/§3.6/§3.7/§3.8).

``make_fused_step`` returns the SAME step as the jnp backend
(``core.batched.make_bitset_step`` / ``make_counter_planes_step``) with one
seam replaced: the filter-sized update. Hashing, the O(B·k) probe gather,
the spec's decision fn, the randomness draw, the event sort, the swbf ring
and position bookkeeping are the jnp step's own code, traced in XLA — so the
two backends are bit-identical by construction. One ``pallas_call`` then
sweeps the VMEM-resident filter in tiles of TW words, writes it in place
(``input_output_aliases``) and counts the exact load delta from the words it
already holds:

* ``bitset`` (rsbf/bsbf/bsbfsd/rlbsbf — packed (k, W) rows): per row,
  ``(A & ~D) | I`` and ``popcount(I & ~A) - popcount(A & D & ~I)``.
* ``counter`` (sbf/swbf/cms/hh — d bit-planes of W words): the spec's
  subtract, then set-to-Max or saturating add, as carry/borrow chains
  (``planes_saturating_sub/add``, ``planes_set_value``), and the change in
  nonzero cells.

XLA reduces the batch's events to filter-sized delta rows (the jnp step's
own ``delta_from_sorted_positions`` / event planes) and the kernel streams
them beside the filter. For the counter family ``cfg.kernel_accumulate``
(DESIGN.md §3.9) instead hands the kernel the sorted per-event word indices
and masks, which it ORs into each tile (``chunk_or``, a tree-OR over
power-of-two chunks); the sentinel word index W matches no lane. Both forms
give the same words. The bitset family has the delta-row form only.

Mosaic (the TPU kernel compiler) constraints the kernel is written to:
every ref is 2-D and read or written by row in ``pl.ds`` tiles; loads leave
the kernel in an SMEM output (VMEM cannot take scalar stores); no gather,
no vector ``dynamic_slice`` and no 1-D-to-2-D shape cast of masks happen in
the kernel. ``check_vmem_budget`` bounds the resident working set (filter
and delta operands) at 8 MiB and ``VMEM_LIMIT_BYTES`` gives the compiler
the room for the in/out copies and temporaries. Off-TPU the kernel runs in
interpret mode and is checked bit-exactly against the jnp step in
tests/test_sketch_template.py; tests/test_tpu_compile.py compiles it for a
v5e.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.batched import make_bitset_step, make_counter_planes_step
from ..core.packed import (clamped_run_counts, delta_from_sorted_positions,
                           planes_nonzero, planes_saturating_add,
                           planes_saturating_sub, planes_set_value)
from .common import (DEFAULT_CHUNK_B, DEFAULT_TILE_W, VMEM_LIMIT_BYTES,
                     check_vmem_budget, chunk_or, counter_vmem_words,
                     event_chunk, largest_tile, popcount_sum)


def make_fused_step(cfg, spec=None, *, tile_w: int = DEFAULT_TILE_W,
                    chunk_b: int = DEFAULT_CHUNK_B,
                    interpret: bool | None = None,
                    params_aware: bool = False):
    """BatchedStep for ``cfg.backend == "pallas"`` — the jnp step of the
    variant's ``SketchSpec`` (or an explicit ``spec``) with the filter
    update in one Pallas kernel; same signature, bit-identical results.
    ``interpret=None`` runs the kernel compiled on a TPU and interpreted
    elsewhere. ``chunk_b`` bounds the event chunk of the counter
    family's accumulate form.

    ``params_aware=True`` is the fleet form (DESIGN §4.6): the step takes a
    trailing ``TenantStepParams``. Its verdict threshold acts in the jnp
    decide; its sbf set-to-Max ceiling rides into the kernel as an SMEM
    scalar. Bitset decisions have no value-like knob, so that family
    accepts and ignores the params."""
    cfg = cfg.validate()
    if spec is None:
        from ..core.sketch import get_spec
        spec = get_spec(cfg.variant)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not cfg.is_planes:
        raise ValueError(
            f"the fused {cfg.variant} kernel needs the bit-plane layout "
            f"(cfg.layout='planes'); got {cfg.effective_layout!r}")
    kw = dict(tile_w=tile_w, chunk_b=chunk_b, interpret=interpret)
    if spec.family == "counter":
        return make_counter_planes_step(
            cfg, spec, params_aware=params_aware,
            apply=_counter_apply(cfg, spec, **kw))
    step = make_bitset_step(cfg, spec, apply=_bitset_apply(cfg, **kw))
    if not params_aware:
        return step
    return lambda state, keys, valid, tp: step(state, keys, valid)


# ------------------------------------------------------------ the kernel //

def _sweep(rows, dense, events, scalars, combine, n_loads, *, tile_w,
           interpret, chunk):
    """One pallas_call over the (R, W) uint32 ``rows``, updated in place.

    ``dense``: (n, W) uint32 delta arrays read tile by tile beside the
    rows. ``events``: (w_idx (1, E) int32, masks (n_m, E) uint32) pairs,
    OR-accumulated per tile into n_m word rows that share the one index
    row, E a multiple of ``chunk``. ``scalars``: () int32 values, read
    from SMEM. ``combine(a, dense_tiles, event_tiles, scalars)`` maps the
    tile's word rows to (new word rows, n_loads int32 load deltas).
    Returns (new rows, (n_loads,) int32 summed load deltas)."""
    n_rows, w = rows.shape
    tw = largest_tile(w, tile_w)
    n_tiles = w // tw

    def accumulate(w_ref, m_ref, base):
        lane = base + jax.lax.iota(jnp.int32, tw)
        n_m = m_ref.shape[0]

        def body(c, accs):
            off = pl.multiple_of(c * chunk, chunk)
            w_idx = w_ref[0, pl.ds(off, chunk)]
            return tuple(
                accs[i] | chunk_or(w_idx, m_ref[i, pl.ds(off, chunk)], lane)
                for i in range(n_m))

        zero = tuple(jnp.zeros((tw,), jnp.uint32) for _ in range(n_m))
        return list(jax.lax.fori_loop(0, m_ref.shape[1] // chunk, body,
                                      zero))

    def kernel(*refs):
        it = iter(refs)
        rows_ref = next(it)
        dense_refs = [next(it) for _ in dense]
        event_refs = [(next(it), next(it)) for _ in events]
        sc = [next(it)[0] for _ in scalars]
        out_ref, dload_ref = next(it), next(it)

        def tile_body(t, dl):
            base = pl.multiple_of(t * tw, tw)
            a = [rows_ref[i, pl.ds(base, tw)] for i in range(n_rows)]
            dt = [[r[i, pl.ds(base, tw)] for i in range(r.shape[0])]
                  for r in dense_refs]
            et = [accumulate(wr, mr, base) for wr, mr in event_refs]
            new, dls = combine(a, dt, et, sc)
            for i in range(n_rows):
                out_ref[i, pl.ds(base, tw)] = new[i]
            return tuple(x + y for x, y in zip(dl, dls))

        dl = jax.lax.fori_loop(0, n_tiles, tile_body,
                               (jnp.int32(0),) * n_loads)
        for i in range(n_loads):
            dload_ref[i] = dl[i]

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    operands = [rows, *dense, *(x for ev in events for x in ev),
                *(jnp.reshape(s, (1,)).astype(jnp.int32) for s in scalars)]
    new_rows, dload = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((n_rows, w), jnp.uint32),
                   jax.ShapeDtypeStruct((n_loads,), jnp.int32)],
        in_specs=[vmem] * (len(operands) - len(scalars))
        + [smem] * len(scalars),
        out_specs=[vmem, smem],
        input_output_aliases={0: 0},          # filter updated in place
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="fused_filter_sweep",
    )(*operands)
    return new_rows, dload


def _event_operands(events, heads, cmax, rows, w, chunk):
    """Sorted event cells -> the accumulate form's kernel operands (§3.9):
    the (1, E) word index and the (rows, E) head-gated contribution masks —
    the exact words the outside ``count_planes_from_sorted`` / set-OR
    scatter would have built, one row per count bit-plane (``cmax`` == 0
    selects the single-bit set-to-Max form). Sentinel events (32·W) land on
    word index W, which matches no tile lane. Padded to a multiple of
    ``chunk``."""
    w_idx = (events >> 5).astype(jnp.int32)
    bit = (events & 31).astype(jnp.uint32)
    if cmax == 0:
        masks = jnp.where(heads, jnp.uint32(1) << bit, jnp.uint32(0))[None]
    else:
        _, cnt = clamped_run_counts(events, cmax)
        cnt = jnp.where(heads, cnt, jnp.uint32(0))
        masks = jnp.stack([((cnt >> p) & jnp.uint32(1)) << bit
                           for p in range(rows)])
    pad = (-events.shape[0]) % chunk
    w_idx = jnp.pad(w_idx, (0, pad), constant_values=w)[None]
    return w_idx, jnp.pad(masks, ((0, 0), (0, pad)))


# ------------------------------------------------- bitset family (k, W) //

def _bitset_apply(cfg, *, tile_w, chunk_b, interpret):
    k, w = cfg.k, cfg.s_words

    def combine(a, dt, et, sc):
        ins, dele = dt
        new, dls = [], []
        for f in range(k):
            i_, d_ = ins[f], dele[f]
            new.append((a[f] & ~d_) | i_)
            dls.append(popcount_sum(i_ & ~a[f])
                       - popcount_sum(a[f] & d_ & ~i_))
        return new, tuple(dls)

    def apply(bits, load, spi, spd):
        dense = [delta_from_sorted_positions(spi, w),
                 delta_from_sorted_positions(spd, w)]
        check_vmem_budget(bits.nbytes + sum(x.nbytes for x in dense),
                          "packed filter + delta rows")
        new, dload = _sweep(bits, dense, [], [], combine, k,
                            tile_w=tile_w, interpret=interpret, chunk=chunk_b)
        return new, load + dload

    return apply


# --------------------------------------------- counter family (d, W) //

def _counter_apply(cfg, spec, *, tile_w, chunk_b, interpret):
    d, w = cfg.n_planes, cfg.s_words
    has_sub, set_mode = spec.has_sub, spec.combine == "set"
    accumulate = cfg.kernel_accumulate
    # saturating subtract/add clamp counts to the plane capacity; set-to-Max
    # events are single OR bits (cmax == 0 selects that form)
    sub_cmax = cfg.sbf_max if set_mode else (1 << d) - 1
    vmem_words = counter_vmem_words(d, has_sub=has_sub, set_mode=set_mode,
                                    accumulate=accumulate)

    def apply(planes, load, ev, cmax):
        static_cmax = isinstance(cmax, (int, np.integer))
        chunk = event_chunk(ev.ins_events.shape[0], chunk_b)
        if accumulate:
            events = []
            if has_sub:
                events.append(_event_operands(ev.sub_events, ev.sub_heads,
                                              sub_cmax, d, w, chunk))
            events.append(_event_operands(
                ev.ins_events, ev.ins_heads, 0 if set_mode else (1 << d) - 1,
                d, w, chunk))
            dense = []
        else:
            events = []
            dense = [ev.sub_planes] if has_sub else []
            dense.append(ev.set_delta[None] if set_mode else ev.add_planes)
        check_vmem_budget(
            vmem_words * w * 4
            + sum(x.nbytes for e in events for x in e),
            f"{cfg.variant} planes + event "
            f"{'operands' if accumulate else 'deltas'}")
        scalars = [] if (static_cmax or not set_mode) else [cmax]

        def combine(a, dt, et, sc):
            ops = iter(et if accumulate else dt)
            r = a
            if has_sub:
                r = planes_saturating_sub(r, next(ops))
            if set_mode:
                r = planes_set_value(r, next(ops)[0],
                                     cmax if static_cmax else sc[0])
            else:
                r = planes_saturating_add(r, next(ops))
            return r, (popcount_sum(planes_nonzero(r))
                       - popcount_sum(planes_nonzero(a)),)

        new, dload = _sweep(planes, dense, events, scalars, combine, 1,
                            tile_w=tile_w, interpret=interpret, chunk=chunk)
        return new, load + dload

    return apply
