"""Shared plumbing for the fused Pallas steps (DESIGN.md §3.4/§3.8).

Every fused ingest kernel — bitset or counter family, hand-written or
generated by ``fused_template.make_fused_step`` — shares the same
VMEM-residency contract and the same in-kernel helpers. They used to be
copy-adapted between ``fused_step.py`` and ``fused_counter_step.py``; this
module is now the single home, and those two files are thin deprecation
shims over the template generator.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..core.packed import popcount_words
from ..launch.hw import VMEM_BYTES

DEFAULT_TILE_W = 512
DEFAULT_CHUNK_B = 1024
VMEM_FILTER_BYTES_LIMIT = 8 * 1024 * 1024
# scoped VMEM the fused kernel may use: the resident set above, its output
# copy, and the tile and event-chunk temporaries. v5e has 128 MiB of VMEM
# (launch/hw.py); the compiler's default scope is smaller.
VMEM_LIMIT_BYTES = 4 * VMEM_FILTER_BYTES_LIMIT
assert VMEM_LIMIT_BYTES <= VMEM_BYTES


def check_vmem_budget(nbytes: int, what: str) -> None:
    """Shared guard for every fused kernel: the filter-resident working set
    must fit the VMEM budget — larger filters shard across devices first
    (repro.dedup.sharded)."""
    if nbytes > VMEM_FILTER_BYTES_LIMIT:
        raise ValueError(
            f"{what} {nbytes} B exceeds the {VMEM_FILTER_BYTES_LIMIT} B VMEM "
            f"budget for the fused step — shard the filter "
            f"(repro.dedup.sharded) first")


def counter_vmem_words(d: int, *, has_sub: bool, set_mode: bool,
                       accumulate: bool) -> int:
    """W-sized VMEM-resident word rows of the fused counter step: the d
    planes, the d subtract planes when the sketch decays, and the insert
    operand — one OR row for set-to-Max, d count planes for saturating add
    (sbf: 2d+1, swbf: 3d, cms/hh: 2d). Accumulate mode (DESIGN.md §3.9)
    swaps every delta plane for per-event operands and keeps only the d
    filter planes resident. Single source of truth shared by the kernel
    generator (fused_template) and the static ``pallas-vmem-budget`` lint
    rule (repro.analysis, DESIGN.md §6) — the two must never disagree."""
    return d + (0 if accumulate else
                (d if has_sub else 0) + (1 if set_mode else d))


def event_chunk(n_events: int, limit: int = DEFAULT_CHUNK_B) -> int:
    """Power-of-two event chunk (>= 8) of the accumulate form's tree-OR:
    the smallest one covering ``min(limit, n_events)``."""
    return 1 << max(3, min(limit, n_events) - 1).bit_length()


def _event_operand_words(n_events: int, rows: int, chunk: int) -> int:
    """Words of one accumulate-mode event operand pair: the (1, E) int32
    word index plus the (rows, E) uint32 masks, E padded up to a multiple
    of ``chunk`` (mirrors ``fused_template._event_operands``)."""
    if n_events <= 0:
        return 0
    padded = n_events + ((-n_events) % chunk)
    return (1 + rows) * padded


def fused_resident_bytes(cfg, batch_size: int | None = None,
                         event_capacity: int | None = None) -> int:
    """Static VMEM working-set of the fused step for ``cfg``, in bytes —
    the compile-free mirror of the ``check_vmem_budget`` call the kernel
    generator makes at trace time, for the ``pallas-vmem-budget`` lint rule
    (DESIGN.md §6): a config can be budget-checked without tracing (and an
    over-budget one without eating its trace-time ValueError).

    ``batch_size`` defaults to ``cfg.batch_size``; ``event_capacity`` is
    the swbf ring's per-slot element count (defaults to the batch size,
    matching ``state.init_ring``)."""
    from ..core.sketch import get_spec   # deferred: core.sketch -> batched
    cfg = cfg.validate()
    w = cfg.s_words
    spec = get_spec(cfg.variant)
    b = cfg.batch_size if batch_size is None else batch_size
    if spec.family == "bitset":
        # the k filter rows plus the insert and delete delta rows
        return 3 * cfg.k * w * 4
    d = cfg.n_planes
    set_mode = spec.combine == "set"
    words = counter_vmem_words(d, has_sub=spec.has_sub, set_mode=set_mode,
                               accumulate=cfg.kernel_accumulate)
    ev_words = 0
    if cfg.kernel_accumulate:
        cap = b if event_capacity is None else event_capacity
        ins_e = (cap if spec.windowed else b) * cfg.k
        chunk = event_chunk(ins_e)
        if spec.has_sub:
            # sbf decays B*P random cells; swbf expires one ring slot
            sub_e = (cap * cfg.k if spec.windowed
                     else b * cfg.sbf_p_effective)
            sub_rows = 1 if (set_mode and cfg.sbf_max == 0) else d
            ev_words += _event_operand_words(sub_e, sub_rows, chunk)
        ins_rows = 1 if set_mode else d
        ev_words += _event_operand_words(ins_e, ins_rows, chunk)
    return (words * w + ev_words) * 4


def fleet_resident_bytes(cfg, capacity: int,
                         event_capacity: int | None = None) -> int:
    """VMEM working-set of the TENANT FLEET's one launch (DESIGN §4.6): the
    fleet vmaps the fused step over the (T, ...) stacked state, so the
    whole fleet's filter planes are resident at once — per-tenant resident
    bytes (at the fleet's slot width C, the per-tenant batch the vmapped
    kernel sees) times ``cfg.n_tenants``. The guard the fused generator
    applies per trace is per TENANT (vmap batches the kernel grid; each
    program instance still touches one tenant's planes), but capacity
    planning and the ``pallas-vmem-budget`` lint rule want the fleet-wide
    figure: thousands of small filters are fine, thousands of near-budget
    ones want the sharded fleet (``dedup.sharded.run_tenant_stream``)."""
    return cfg.n_tenants * fused_resident_bytes(
        cfg, batch_size=capacity, event_capacity=event_capacity)


def largest_tile(w: int, limit: int) -> int:
    """Largest tile width <= limit that divides w (the fused kernels sweep
    the W words in equal tiles)."""
    tw = min(limit, w)
    while w % tw:
        tw -= 1
    return tw


def popcount_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Total set bits of a uint32 vector -> int32 scalar (traced in-kernel;
    same word algebra as the jnp steps by construction)."""
    return popcount_words(x).sum()


def chunk_or(w_idx: jnp.ndarray, masks: jnp.ndarray, lane: jnp.ndarray
             ) -> jnp.ndarray:
    """OR-union of single-bit masks onto a word tile: (C,) idx/mask vs (TW,)
    lane iota -> (TW,) uint32. C is a power of two (tree-OR)."""
    eq = w_idx[:, None] == lane[None, :]
    x = jnp.where(eq, masks[:, None], jnp.uint32(0))
    while x.shape[0] > 1:
        half = x.shape[0] // 2
        x = x[:half] | x[half:]
    return x[0]

