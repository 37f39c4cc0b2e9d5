"""Public de-duplication engine.

    cfg   = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 23)
    dedup = Dedup(cfg)
    state = dedup.init()
    state, dup = dedup.process(state, keys)          # batched, jitted
    state, dup = dedup.run_stream(state, long_keys)  # auto-batched scan
    state, dup = dedup.run_stream_oracle(state, keys)  # sequential reference

All entry points are functionally pure: state in, state out — which is what
lets the same engine run under pjit/shard_map (see repro.dedup.sharded) and be
checkpointed mid-stream (see repro.checkpoint).

Contract and state layout: an engine is fully determined by its frozen
``DedupConfig``; the state it threads is the ``FilterState`` pytree — bits
in the configured cell layout (dense8 bytes or packed bit-planes,
DESIGN.md §3.6), the 1-indexed stream position, the exact incrementally
tracked load (§3.1), the rng, and the optional swbf window ring (§3.7).
At fixed seed, dup reports are deterministic across refactors and
bit-identical between the jnp and pallas backends (§3.4); batched-vs-
oracle divergence is bounded per DESIGN.md §2.

Compile caching (DESIGN.md §3.5): every jitted callable is built once in
``__init__`` and reused across calls — ``run_stream`` re-running the same
stream length never re-traces (regression-tested via ``stream_cache_size``).
``run_stream`` additionally *donates* the input state, so XLA aliases the
k·s-bit filter buffer in place across the whole scan instead of copying it:
do not reuse a state object after passing it to ``run_stream`` (thread the
returned state instead, as every call site here does). ``process`` does NOT
donate — interactive callers commonly probe a state and keep it.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compat import jit_cache_size
from ..tracing import span
from .batched import BatchResult, make_batched_step, make_estimate_fn
from .config import DedupConfig
from .packed import unpack_cells
from .state import FilterState, init_state
from .variants import make_scan_step


def next_pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1)."""
    return 1 << max(0, (int(n) - 1).bit_length())


class Dedup:
    def __init__(self, cfg: DedupConfig):
        self.cfg = cfg.validate()
        self._step = make_batched_step(cfg)
        self._batched = jax.jit(self._step)
        self._batched_donated = jax.jit(self._step, donate_argnums=0)
        if cfg.effective_layout == "dense8":
            self._scan_step = make_scan_step(cfg)
        if cfg.is_counter and cfg.effective_layout == "planes":
            self._estimate = jax.jit(make_estimate_fn(cfg))
        self._stream = jax.jit(self._stream_impl, donate_argnums=0)

    # ------------------------------------------------------------------ //
    def init(self, seed: int | None = None,
             event_capacity: int | None = None) -> FilterState:
        """``event_capacity`` (swbf only) widens the state ring's per-slot
        event list beyond the default ``cfg.batch_size`` elements — needed
        when ``process`` will be driven with wider batches (DESIGN §3.7)."""
        return init_state(self.cfg, seed, event_capacity=event_capacity)

    def process(self, state: FilterState, keys: jnp.ndarray,
                valid: jnp.ndarray | None = None
                ) -> Tuple[FilterState, BatchResult]:
        """One batched step. keys (B,) uint32. For the windowed variant
        (swbf) the batch must fit the state ring's event capacity — one ring
        slot absorbs one step's events (DESIGN §3.7)."""
        if state.ring is not None:
            cap = state.ring.events.shape[-1] // self.cfg.k
            if keys.shape[0] > cap:
                raise ValueError(
                    f"swbf batch of {keys.shape[0]} exceeds the state ring's "
                    f"event capacity {cap} — init the state with "
                    f"event_capacity >= the batch width, or batch at "
                    f"cfg.batch_size={self.cfg.batch_size}")
        if valid is None:
            valid = jnp.ones(keys.shape, dtype=bool)
        return self._batched(state, keys.astype(jnp.uint32), valid)

    def process_padded(self, state: FilterState, keys,
                       valid=None, *, width: int | None = None,
                       donate: bool = False
                       ) -> Tuple[FilterState, BatchResult]:
        """Shape-stable ``process``: pad ``(keys, valid)`` with invalid
        lanes up to ``width`` so EVERY ragged request length reuses one
        compiled trace per distinct width (the serving front-end's batch
        buckets, DESIGN.md §5.2) instead of re-tracing the jitted step per
        length. Invalid lanes are never routed, inserted, or counted
        (DESIGN.md §2 valid-mask semantics); the returned ``BatchResult``
        is sliced back to the request length.

        ``width`` defaults to ``max(cfg.batch_size, next_pow2(n))``.
        ``donate=True`` routes through a state-donating jit so the filter
        buffer is aliased in place (the front-end threads its state and
        never reuses the argument); the passed ``state`` is invalidated.

        Note the determinism contract: the per-step randomness is drawn at
        the PADDED width, so verdicts are reproducible per (schedule,
        width) — replaying the same batches at the same widths is
        bit-identical, re-bucketing is not (DESIGN.md §5.2).
        """
        n = int(keys.shape[0])
        if width is None:
            width = max(self.cfg.batch_size, next_pow2(n))
        if n > width:
            raise ValueError(f"batch of {n} exceeds pad width {width}")
        xp = np if isinstance(keys, np.ndarray) else jnp
        keys_p = xp.pad(keys.astype(xp.uint32), (0, width - n))
        if valid is None:
            valid = xp.ones((n,), bool)
        valid_p = xp.pad(xp.asarray(valid, dtype=bool), (0, width - n))
        if state.ring is not None:
            cap = state.ring.events.shape[-1] // self.cfg.k
            if width > cap:
                raise ValueError(
                    f"pad width {width} exceeds the state ring's event "
                    f"capacity {cap} — init the state with "
                    f"event_capacity >= the widest bucket (DESIGN §3.7)")
        fn = self._batched_donated if donate else self._batched
        state, res = fn(state, jnp.asarray(keys_p), jnp.asarray(valid_p))
        if width != n:
            res = BatchResult(*(x[:n] for x in res))
        return state, res

    def process_cache_size(self) -> int:
        """Compiled specializations of the batched step (one per distinct
        padded width × donation flag) — the no-recompile regression probe
        for the serving front-end's bucket contract (DESIGN.md §5.2)."""
        return (jit_cache_size(self._batched)
                + jit_cache_size(self._batched_donated))

    # ------------------------------------------------------------------ //
    def estimate(self, state: FilterState, keys: jnp.ndarray) -> jnp.ndarray:
        """Serve-path frequency readout (counter-family, plane layout):
        (B,) int32 count-min estimates — MIN over the k probed d-bit cells
        (DESIGN.md §3.8). Read-only: no state change, no rng consumption,
        so interactive callers can probe a state they keep. For cms the
        estimate never under-counts while the probed cells are below the
        2^d - 1 cap; for sbf/swbf it reads the decayed/windowed counters."""
        if not hasattr(self, "_estimate"):
            raise ValueError(
                f"estimate() needs a counter-family variant on the plane "
                f"layout (sbf/swbf/cms/hh); got {self.cfg.variant!r} on "
                f"{self.cfg.effective_layout!r}")
        return self._estimate(state, keys.astype(jnp.uint32))

    def top_cells(self, state: FilterState, m: int = 16
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Heavy-load monitoring readout (counter-family, plane layout):
        the ``m`` highest-valued cells as (cells (m,) i32, counts (m,) i32),
        sorted descending (DESIGN.md §3.8). A cell's count upper-bounds the
        total frequency of every key hashing into it, so for the hh sketch
        these are the candidate heavy-hitter buckets StreamMetrics surfaces.
        O(s) readout — a monitoring probe, not a hot-path op."""
        if not (self.cfg.is_counter
                and self.cfg.effective_layout == "planes"):
            raise ValueError(
                f"top_cells() needs a counter-family variant on the plane "
                f"layout (sbf/swbf/cms/hh); got {self.cfg.variant!r} on "
                f"{self.cfg.effective_layout!r}")
        counts, cells = _top_cells_impl(state.bits, self.cfg.s, m)
        return cells, counts

    # ------------------------------------------------------------------ //
    def _stream_impl(self, state: FilterState, kb: jnp.ndarray,
                     vb: jnp.ndarray):
        def body(st, xs):
            kk, vv = xs
            st, res = self._step(st, kk, vv)
            return st, res.dup

        return jax.lax.scan(body, state, (kb, vb))

    def run_stream(self, state: FilterState, keys: jnp.ndarray
                   ) -> Tuple[FilterState, jnp.ndarray]:
        """Batched engine over a whole (N,) stream via lax.scan; tail padded
        with invalid lanes. Returns per-element duplicate reports.

        The input ``state`` is donated (updated in place) — use the returned
        state afterwards, never the argument. The ``dedup.stream.enqueue``
        span times the host's part: pad, reshape and the scan's dispatch."""
        with span("dedup.stream.enqueue"):
            b = self.cfg.batch_size
            n = keys.shape[0]
            n_pad = (-n) % b
            keys_p = jnp.pad(keys.astype(jnp.uint32), (0, n_pad))
            valid = jnp.pad(jnp.ones((n,), bool), (0, n_pad))
            kb = keys_p.reshape(-1, b)
            vb = valid.reshape(-1, b)
            state, dups = self._stream(state, kb, vb)
            return state, dups.reshape(-1)[:n]

    def stream_cache_size(self) -> int:
        """Number of compiled specializations of the stream scan (one per
        distinct stream length) — used by the no-recompile regression test."""
        return jit_cache_size(self._stream)

    def run_stream_oracle(self, state: FilterState, keys: jnp.ndarray
                          ) -> Tuple[FilterState, jnp.ndarray]:
        """Sequential per-element oracle (paper pseudocode order)."""
        if self.cfg.effective_layout != "dense8":
            raise ValueError("oracle runs on the dense8 layout")
        state, dups = jax.lax.scan(
            self._scan_step, state, keys.astype(jnp.uint32))
        return state, dups


@functools.partial(jax.jit, static_argnums=(1, 2))
def _top_cells_impl(bits: jnp.ndarray, s: int, m: int):
    planes = bits if bits.ndim == 3 else bits[None]
    values = unpack_cells(planes[:, 0, :], s)                 # (s,) i32
    return jax.lax.top_k(values, m)                           # (counts, cells)


@functools.lru_cache(maxsize=64)
def _cached_engine(cfg: DedupConfig) -> Dedup:
    return Dedup(cfg)


def get_engine(cfg: DedupConfig) -> Dedup:
    """Engines are stateless w.r.t. streams and cache their jitted callables,
    so they are shared: keyed on the *frozen* ``DedupConfig`` dataclass (all
    fields participate in __eq__/__hash__ — two configs differing in any
    engine knob get distinct engines; equal configs reuse one engine and its
    compiled steps)."""
    return _cached_engine(cfg)
