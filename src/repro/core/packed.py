"""uint32-packed cell operations — the word side of the plane layout.

TPUs have no efficient random single-bit scatter; the plane layout stores 32
cells per lane word (d bit-planes per cell, d == 1 for plain bits) and
performs:

  * probe:   word gather (lowers to dynamic-slice) + mask test — multi-plane
    states OR their planes' gathered words first (nonzero test)
  * set/clear scatter: sort the batch's word indices, OR together the
    single-bit masks of each equal-index run with one segmented scan, and
    scatter exactly one uint32 per touched word (``_bit_delta_rows``). This is
    O(B log B) work and O(B) scatter entries — no per-bit decomposition, no
    (B·k, 32) uint8 intermediate (DESIGN.md §3.2).
  * counter arithmetic (DESIGN.md §3.6): saturating increment/decrement and
    set-to-value expressed as carry/borrow chains of the same
    ``(A & ~D) | I`` word ops — ``planes_saturating_sub/add``,
    ``planes_set_value`` — so SBF's counters ride the exact machinery the
    1-bit variants already use.

The Pallas kernels in ``repro.kernels`` implement the same contracts with
explicit VMEM tiling; these jnp forms are their oracles and the fallback path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "pack_bits", "unpack_bits", "split_pos", "probe_packed",
    "probe_cell_values",
    "delta_from_sorted_positions", "probe_sorted_packed",
    "scatter_or", "scatter_andnot", "popcount", "popcount_words",
    "pack_cells", "unpack_cells", "planes_nonzero",
    "count_field_chunks", "counts_to_planes",
    "run_heads_1d", "clamped_run_counts", "count_planes_from_sorted",
    "planes_saturating_sub", "planes_saturating_add", "planes_set_value",
]

_BIT = jnp.uint32(1)


def split_pos(pos: jnp.ndarray):
    """bit position -> (word index int32, single-bit uint32 mask)."""
    word = (pos // 32).astype(jnp.int32)
    mask = (_BIT << (pos % 32).astype(jnp.uint32)).astype(jnp.uint32)
    return word, mask


def pack_bits(bits8: jnp.ndarray) -> jnp.ndarray:
    """(..., s) uint8 {0,1} -> (..., ceil(s/32)) uint32."""
    s = bits8.shape[-1]
    pad = (-s) % 32
    if pad:
        bits8 = jnp.pad(bits8, [(0, 0)] * (bits8.ndim - 1) + [(0, pad)])
    b = bits8.reshape(*bits8.shape[:-1], -1, 32).astype(jnp.uint32)
    weights = (_BIT << jnp.arange(32, dtype=jnp.uint32)).astype(jnp.uint32)
    return (b * weights).sum(axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jnp.ndarray, s: int) -> jnp.ndarray:
    """(..., W) uint32 -> (..., s) uint8 {0,1}."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    b = (words[..., None] >> shifts) & _BIT
    return b.reshape(*words.shape[:-1], -1)[..., :s].astype(jnp.uint8)


def probe_packed(words: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """words (k, W), pos (..., k) -> (..., k) uint8 bit values.
    Gather each filter's word then test the bit."""
    k = words.shape[0]
    w_idx, mask = split_pos(pos)
    rows = jnp.arange(k, dtype=jnp.int32)
    got = words[rows, w_idx]                      # (..., k) gather per filter
    return ((got & mask) != 0).astype(jnp.uint8)


def probe_cell_values(planes: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """planes (d, W), pos (..., k) cell positions -> (..., k) int32 cell
    VALUES. One word gather per plane (d total), bit test, shift-OR into the
    d-bit value — the value-probe op of the counting sketches (cms/hh
    frequency estimates, DESIGN.md §3.8). At d == 1 this is the plain
    membership probe."""
    w_idx, mask = split_pos(pos)
    vals = jnp.zeros(pos.shape, jnp.int32)
    for p in range(planes.shape[0]):
        bit = (planes[p][w_idx] & mask) != 0
        vals = vals | (bit.astype(jnp.int32) << p)
    return vals


def _segmented_or(head: jnp.ndarray, vals: jnp.ndarray):
    """Inclusive segmented OR-scan along the last axis.

    head (..., n) bool — True where a new segment starts; vals (..., n)
    uint32. Returns (..., n) uint32 where each element is the OR of its
    segment's prefix. The standard segmented-scan monoid is associative, so
    this lowers to log2(n) vector passes.
    """
    def comb(a, b):
        ha, va = a
        hb, vb = b
        return ha | hb, jnp.where(hb, vb, va | vb)

    _, acc = jax.lax.associative_scan(comb, (head, vals), axis=-1)
    return acc


def run_heads(sp: jnp.ndarray) -> jnp.ndarray:
    """(k, B) sorted -> True at the first element of each equal-value run."""
    k = sp.shape[0]
    return jnp.concatenate(
        [jnp.ones((k, 1), bool), sp[:, 1:] != sp[:, :-1]], axis=1)


def _scatter_run_or(sw: jnp.ndarray, sm: jnp.ndarray, W: int) -> jnp.ndarray:
    """(k, B) *sorted* word indices + aligned masks -> (k, W) uint32 delta:
    segmented-OR each equal-index run, scatter one word per run tail.
    Indices >= W (disabled-lane sentinels) are dropped by the scatter."""
    k = sw.shape[0]
    head = run_heads(sw)
    acc = _segmented_or(head, sm)
    tail = jnp.concatenate(
        [sw[:, :-1] != sw[:, 1:], jnp.ones((k, 1), bool)], axis=1)
    idx = jnp.where(tail, sw, W)                             # non-tails dropped
    rows = jnp.arange(k, dtype=jnp.int32)[:, None]
    return jnp.zeros((k, W), jnp.uint32).at[rows, idx].set(
        jnp.where(tail, acc, jnp.uint32(0)), mode="drop")


def _bit_delta_rows(W: int, w_idx: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Per-row OR-union of single-bit masks: (B, k) -> (k, W) uint32 delta.

    Sort each row's word indices, segmented-OR the masks of equal-index runs,
    then scatter one word per run tail. Disabled lanes use w_idx >= W and are
    dropped by the scatter. O(B log B) sort + O(B) scatter — the load-bearing
    replacement for the per-bit (B, 32) expansion (DESIGN.md §3.2).
    """
    k = w_idx.shape[-1]
    wT = w_idx.reshape(-1, k).T                              # (k, B)
    mT = mask.reshape(-1, k).T
    order = jnp.argsort(wT, axis=-1)
    sw = jnp.take_along_axis(wT, order, axis=-1)
    sm = jnp.take_along_axis(mT, order, axis=-1)
    return _scatter_run_or(sw, sm, W)


def delta_from_sorted_positions(sp: jnp.ndarray, W: int) -> jnp.ndarray:
    """(k, B) *sorted* bit positions -> (k, W) uint32 OR-union delta.

    Word indices and single-bit masks are derived from the already-sorted
    positions (so word runs are contiguous for free — no argsort, no
    permutation), OR-combined per word run with one segmented scan, and
    scattered one uint32 per touched word. Disabled lanes must carry a
    sentinel position >= 32*W: their word index lands at W and the scatter
    drops it. This is the hot-path delta builder (DESIGN.md §3.2).
    """
    sw = (sp >> 5).astype(jnp.int32)                         # sentinel -> >= W
    sm = (_BIT << (sp & 31).astype(jnp.uint32)).astype(jnp.uint32)
    return _scatter_run_or(sw, sm, W)


def probe_sorted_packed(words: jnp.ndarray, sp: jnp.ndarray) -> jnp.ndarray:
    """Row-aligned probe: words (k, W), sp (k, B) positions (row f probes its
    own row — unlike ``probe_packed``'s (B, k) element-major layout).
    Sentinel positions read a clamped word; mask the result with ``sp < s``.
    """
    k, W = words.shape
    rows = jnp.arange(k, dtype=jnp.int32)[:, None]
    sw = jnp.minimum((sp >> 5).astype(jnp.int32), W - 1)
    got = words[rows, sw]
    return ((got >> (sp & 31).astype(jnp.uint32)) & _BIT).astype(jnp.uint8)


def scatter_or(words: jnp.ndarray, w_idx: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Set bits: words (k, W); w_idx/mask (..., k). Out-of-range idx drop
    (used to express per-element enable masks)."""
    _, W = words.shape
    return words | _bit_delta_rows(W, w_idx, mask)


def scatter_andnot(words: jnp.ndarray, w_idx: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Clear bits (same contract as scatter_or)."""
    _, W = words.shape
    return words & ~_bit_delta_rows(W, w_idx, mask)


def popcount_words(words: jnp.ndarray) -> jnp.ndarray:
    """Elementwise per-word population count: uint32 -> int32, same shape."""
    x = words
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    x = (x * jnp.uint32(0x01010101)) >> 24
    return x.astype(jnp.int32)


def popcount(words: jnp.ndarray) -> jnp.ndarray:
    """Per-row population count: (k, W) uint32 -> (k,) int32."""
    return popcount_words(words).sum(axis=-1)


# ------------------------------------------------------------------ planes //
# Counter cells as d uint32 bit-planes (DESIGN.md §3.6): plane p holds bit p
# of every cell's value, 32 cells per lane word. All arithmetic below is
# pure word-parallel boolean algebra — the "scatter" halves stay the delta
# machinery above; these are the elementwise combine laws.

def pack_cells(cells: jnp.ndarray, d: int) -> jnp.ndarray:
    """(..., s) integer cells in [0, 2^d) -> (d, ..., W) uint32 bit-planes."""
    cells = cells.astype(jnp.uint32)
    return jnp.stack(
        [pack_bits(((cells >> p) & jnp.uint32(1)).astype(jnp.uint8))
         for p in range(d)])


def unpack_cells(planes: jnp.ndarray, s: int) -> jnp.ndarray:
    """(d, ..., W) uint32 bit-planes -> (..., s) int32 cell values."""
    out = None
    for p in range(planes.shape[0]):
        bit = unpack_bits(planes[p], s).astype(jnp.int32) << p
        out = bit if out is None else out + bit
    return out


def planes_nonzero(planes: jnp.ndarray) -> jnp.ndarray:
    """(d, ..., W) -> (..., W) uint32 word with bit j set iff cell j != 0.
    Python-unrolled OR — no reduce op over any filter-sized axis. A list
    of d word rows works too."""
    nz = planes[0]
    for p in range(1, len(planes)):
        nz = nz | planes[p]
    return nz


def count_field_chunks(d: int) -> int:
    """Chunk words per filter word for the d-bit count-field accumulator."""
    return -(-32 // (32 // d))


def counts_to_planes(acc: jnp.ndarray, d: int, w: int) -> jnp.ndarray:
    """(W·n_chunks,) uint32 count-field accumulator -> (d, W) bit-planes.

    The scatter side packs each cell's clamped count as a d-bit field:
    chunk word ``w·n_chunks + c`` holds cells ``[c·cpc, (c+1)·cpc)`` of
    filter word w at bit offsets ``d·t_local`` (cpc = 32 // d cells per
    chunk). One field per cell means one scatter-ADD entry per touched cell
    — no read-modify-write, no segmented scan. This function is the pure
    elementwise unscramble back to bit-plane form; d == 2 (Max = 2..3, the
    Deng & Rafiei setting) takes a 5-step bit-compaction fast path.
    """
    if d == 1:
        return acc.reshape(1, w)
    nc = count_field_chunks(d)
    a = acc.reshape(w, nc)
    if d == 2:
        planes = []
        for q in range(2):
            halves = []
            for c in range(2):
                x = (a[:, c] >> q) & jnp.uint32(0x55555555)
                x = (x | (x >> 1)) & jnp.uint32(0x33333333)
                x = (x | (x >> 2)) & jnp.uint32(0x0F0F0F0F)
                x = (x | (x >> 4)) & jnp.uint32(0x00FF00FF)
                x = (x | (x >> 8)) & jnp.uint32(0x0000FFFF)
                halves.append(x)
            planes.append(halves[0] | (halves[1] << 16))
        return jnp.stack(planes)
    cpc = 32 // d
    planes = []
    for q in range(d):
        p = jnp.zeros((w,), jnp.uint32)
        for t in range(32):
            c, tl = t // cpc, t % cpc
            p = p | (((a[:, c] >> (d * tl + q)) & jnp.uint32(1)) << t)
        planes.append(p)
    return jnp.stack(planes)


def run_heads_1d(sp: jnp.ndarray) -> jnp.ndarray:
    """(n,) sorted -> True at the first event of each equal-value run."""
    return jnp.concatenate([jnp.ones((1,), bool), sp[1:] != sp[:-1]])


def clamped_run_counts(sp: jnp.ndarray, cmax: int):
    """(n,) *sorted* event cells -> (head, cnt): run-head flags and each
    event's run length clamped to ``cmax`` (exact at every head once
    clamped — the only places the count is consumed). Shared by SBF's
    decrement runs and SWBF's insert events (DESIGN.md §3.6/§3.7).

    Small caps read the count off with cmax-1 shifted equality compares;
    wide caps (> 16, e.g. cbf_bits=8's 255) would unroll into hundreds of
    full-width vector passes, so they take two binary searches of the
    sorted array against itself instead (exact run lengths, O(n log n)).
    Identical outputs either way."""
    n = sp.shape[0]
    if cmax <= 1:
        return run_heads_1d(sp), jnp.ones((n,), jnp.uint32)
    if cmax - 1 > 16:
        lo = jnp.searchsorted(sp, sp, side="left")
        hi = jnp.searchsorted(sp, sp, side="right")
        cnt = jnp.minimum((hi - lo).astype(jnp.uint32), jnp.uint32(cmax))
        return run_heads_1d(sp), cnt
    cnt = jnp.ones((n,), jnp.uint32)
    ext = jnp.concatenate([sp, jnp.full((cmax - 1,), -1, sp.dtype)])
    for r in range(1, cmax):
        cnt = cnt + (sp == ext[r:r + n]).astype(jnp.uint32)
    return run_heads_1d(sp), cnt


def count_planes_from_sorted(sp: jnp.ndarray, head: jnp.ndarray,
                             cnt: jnp.ndarray, d: int, w: int) -> jnp.ndarray:
    """Sorted event cells + clamped head counts -> (d, W) count bit-planes.

    Heads are unique per cell, so every strategy below is one collision-free
    scatter-ADD per event — no read-modify-write, no segmented scan; the
    choice is only about post-scatter work:

      * d <= 2: scatter each count once as a d-bit field in the chunked
        accumulator layout and unscramble with ``counts_to_planes`` (whose
        d == 2 bit-compaction fast path is a handful of W-passes);
      * d > 2: scatter each count's d plane bits as one (E, d) row in a
        SINGLE scatter into a (W, d) accumulator (a multi-feature scatter
        costs the same as a 1-D one), then transpose — O(E) scatter entries
        + one O(d·W) transpose pass, ZERO filter-sized unscramble work.
        The generic ``counts_to_planes`` loop is O(32·d·W) element ops,
        which at paper-scale W dwarfs the event buffers and erases the
        layout's win (measured in benchmarks/window_throughput.py).

    Both forms produce bit-identical planes (they encode the same exact
    counts). Sentinel cells (>= 32·W) land past the buffers, dropped."""
    if d <= 2:
        cpc = 32 // d
        nc = count_field_chunks(d)
        t = (sp & 31).astype(jnp.uint32)
        fidx = (sp >> 5) * nc + (t // cpc).astype(jnp.int32)  # sent -> >= W·nc
        fval = jnp.where(head, cnt, jnp.uint32(0)) << (d * (t % cpc))
        acc = jnp.zeros((w * nc,), jnp.uint32).at[fidx].add(fval, mode="drop")
        return counts_to_planes(acc, d, w)
    t = (sp & 31).astype(jnp.uint32)
    widx = sp >> 5                                         # sentinel -> >= W
    masked = jnp.where(head, cnt, jnp.uint32(0))
    vals = jnp.stack([((masked >> q) & jnp.uint32(1)) << t
                      for q in range(d)], axis=1)          # (E, d)
    acc = jnp.zeros((w, d), jnp.uint32).at[widx].add(vals, mode="drop")
    return acc.T


def planes_saturating_sub(planes: jnp.ndarray, counts: jnp.ndarray
                          ) -> jnp.ndarray:
    """Per-cell ``max(value - count, 0)`` as a borrow chain of word ops.

    planes (d, ..., W): value bit-planes; counts (d, ..., W): subtrahend
    bit-planes, each count already clamped into [0, 2^d) (clamping to Max is
    lossless for the saturated result since value <= Max). The final borrow
    word marks cells where count exceeded the value — those saturate to 0.
    Either operand may also be a list of d word rows (the fused kernel's
    tiles, which Mosaic cannot stack); the result is then a list too.
    """
    d = len(planes)
    assert len(counts) == d, (d, len(counts))
    borrow = jnp.zeros_like(planes[0])
    diffs = []
    for p in range(d):
        a, c = planes[p], counts[p]
        diffs.append(a ^ c ^ borrow)
        borrow = (~a & (c | borrow)) | (c & borrow)
    return _like(planes, [dp & ~borrow for dp in diffs])


def _like(planes, rows):
    """``rows`` stacked when ``planes`` is an array, a list when a list."""
    return list(rows) if isinstance(planes, (list, tuple)) else jnp.stack(rows)


def planes_saturating_add(planes: jnp.ndarray, addend: jnp.ndarray
                          ) -> jnp.ndarray:
    """Per-cell ``min(value + addend, 2^d - 1)`` as a carry chain of word
    ops (the increment dual of ``planes_saturating_sub``; counting-filter
    building block). Overflowing cells saturate to the all-ones value.
    Lists of word rows are accepted as in ``planes_saturating_sub``."""
    d = len(planes)
    assert len(addend) == d, (d, len(addend))
    carry = jnp.zeros_like(planes[0])
    sums = []
    for p in range(d):
        a, c = planes[p], addend[p]
        sums.append(a ^ c ^ carry)
        carry = (a & c) | (a & carry) | (c & carry)
    return _like(planes, [sp | carry for sp in sums])


def planes_set_value(planes: jnp.ndarray, delta: jnp.ndarray, value
                     ) -> jnp.ndarray:
    """Set every cell selected by the OR-union ``delta`` word to ``value``:
    plane p gets ``(A | delta)`` where value's bit p is 1, ``(A & ~delta)``
    where it is 0 — the same one-pass ``(A & ~D) | I`` form as the 1-bit
    update (DESIGN.md §3.2/§3.6).

    ``value`` may be a Python int (static — the per-plane branch folds at
    trace time) or a traced int32 scalar (per-tenant ``Max`` broadcast,
    DESIGN §4.6): ``(A & ~D) | (D & mask_p)`` with ``mask_p`` the all-ones
    word iff value's bit p is set — identical words, data-dependent value."""
    if isinstance(value, (int, np.integer)):
        return _like(planes,
                     [(planes[p] | delta) if (int(value) >> p) & 1
                      else (planes[p] & ~delta) for p in range(len(planes))])
    vdyn = jnp.asarray(value, jnp.uint32)
    out = []
    for p in range(len(planes)):
        mask_p = jnp.uint32(0) - ((vdyn >> p) & jnp.uint32(1))
        out.append((planes[p] & ~delta) | (delta & mask_p))
    return _like(planes, out)
