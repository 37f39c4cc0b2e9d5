"""Distributed de-duplication: key-space-partitioned filters over the mesh.

The paper leaves parallelization as future work (Section 7). This module is
the beyond-paper distribution design (DESIGN.md §4):

  * The key space is partitioned into ``n_shards`` ranges by an independent
    router hash; shard ``j`` holds a full k-filter structure of ``s/n_shards``
    bits per filter and is *authoritative* for its range. The ensemble is
    bit-identical to one giant filter of the aggregate size — sharding changes
    the layout, not the math (FPR/FNR follow the aggregate s).
  * Every device processes a local slice of the stream, routes each key to
    its owner with a fixed-capacity MoE-style dispatch (build (S, C) buffers,
    ``jax.lax.all_to_all``, dedup locally, all_to_all the verdicts back).
  * Capacity overflow (Poisson tail) is *conservatively reported distinct*
    and counted — at capacity_factor=2 the overflow rate is < 1e-6 for
    B/S >= 16; the monitor in metrics.py tracks it.
  * The per-shard work is the SAME batched step as the single-device engine
    (``core.batched.make_batched_step``) — including the exact incremental
    load tracking (§3.1), the fused Pallas backend when
    ``base.backend="pallas"``, and SBF's counter-plane layout with its
    fused counter kernel (§3.6) — applied below the leading shard axis.
    The plane-stacked ``(d, 1, W)`` SBF state rides the generic pytree
    plumbing (shard axis prepended, donated, aliased) untouched.
  * ``run_stream`` mirrors the single-device engine (§3.5): one cached
    jitted ``lax.scan`` over batches with the sharded ``FilterState``
    *donated* and aliased in place, so a multi-batch sharded stream is ONE
    dispatch instead of one per batch; per-batch duplicate verdicts and
    overflow counters accumulate device-side (read out lazily via
    ``dedup.metrics.StreamMetrics``).

Two routing modes share the service (DESIGN §4.4):

  * **Static hash routing** (default, ``cfg.rebalance_buckets == 0``): the
    historical path above — an independent router hash balances the key
    space in expectation, each shard is one filter.
  * **Elastic key-range routing** (``cfg.rebalance_buckets = n_buckets``):
    the uint32 key space splits into ``n_buckets`` contiguous ranges, each
    range a self-contained sub-filter (own bits/position/load/rng/ring)
    sized ``memory/n_buckets``; a replicated router table
    (``FilterState.router``) maps buckets to shards. A per-batch load
    monitor inside the cached scan watches the max/mean per-shard load
    ratio; when it crosses ``cfg.rebalance_threshold`` the scan body
    re-packs the table (greedy LPT, replicated + deterministic) and moves
    whole bucket sub-filters between devices over a STATIC
    ``collective_permute`` ring schedule gated by ``lax.cond``
    (``distributed.sharding.rebalance_collect``). Every per-bucket
    computation — probes, rng draws, positions, ring slots — travels with
    its bucket, so a re-partition changes *placement, not math*: dup
    verdicts are bit-identical to never having rebalanced, and to a
    single-device oracle holding all buckets (tests/test_rebalance.py).

All version-sensitive jax surfaces (``shard_map``, the ambient mesh,
``ppermute``) go through ``repro.compat`` — never the raw API (pinned-jax
policy, DESIGN §4).

Exactness within a step: keys landing on their owner in the same step window
are cross-deduplicated by the batched engine's intra-batch matching — the
same semantics a single giant filter would give under the batched engine.
Ragged stream tails ride through as ``valid``-masked lanes: an invalid lane
is never routed, never counted as overflow, and never inserted.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import compat
from ..core.batched import BatchResult, make_batched_step
from ..core.config import DedupConfig
from ..core.hashing import range_bucket, route_hash
from ..core.sketch import get_spec
from ..core.state import (FilterState, RouterState, WindowRing, init_router,
                          init_state)
from ..distributed.sharding import rebalance_collect

_INT32_MAX = np.iinfo(np.int32).max


class InFlight(NamedTuple):
    """One dispatched-but-not-consumed batch — the second stage of the
    pipelined scan carry (DESIGN §4.5). ``keys``/``cnt`` are the
    POST-all_to_all receive buffers (per-source key windows and valid-lane
    counts for the shard this device owns); ``o``/``sl``/``p``/``keep`` are
    the home-side gather coordinates needed to route the verdicts back when
    the batch is consumed one scan iteration later; ``ovf`` carries the
    dispatch-side overflow count so it can be emitted next to the batch's
    verdicts. ``sl`` is None on the static path (no bucket slots)."""
    keys: jnp.ndarray                 # (1, S, C) / (1, S, b_r, C) uint32
    cnt: jnp.ndarray                  # (1, S) / (1, S, b_r) int32
    o: jnp.ndarray                    # (1, b) int32 destination shard
    sl: Optional[jnp.ndarray]         # (1, b) int32 bucket slot (elastic)
    p: jnp.ndarray                    # (1, b) int32 window position
    keep: jnp.ndarray                 # (1, b) bool  routed (not overflowed)
    ovf: jnp.ndarray                  # (1,)   int32 dispatch-side overflow


@dataclasses.dataclass(frozen=True)
class ShardedDedupConfig:
    base: DedupConfig
    mesh_axes: Tuple[str, ...] = ("data", "model")   # axes the filter shards span
    capacity_factor: float = 2.0
    pipeline: bool = True          # double-buffered dispatch (DESIGN §4.5)

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        """The stream batch must be split over every axis the filters span —
        a key processed by two replicas would double-report."""
        return self.mesh_axes

    @property
    def elastic(self) -> bool:
        """Elastic key-range routing with a dynamic router table (§4.4) —
        selected by ``base.rebalance_buckets > 0``."""
        return self.base.rebalance_buckets > 0

    @property
    def n_buckets(self) -> int:
        return self.base.rebalance_buckets

    def n_shards(self, mesh: Mesh) -> int:
        return int(np.prod([mesh.shape[a] for a in self.mesh_axes]))

    def capacity(self, local_batch: int, mesh: Mesh) -> int:
        s = self.n_shards(mesh)
        c = math.ceil(local_batch / s * self.capacity_factor)
        return max(8, c)

    def bucket_capacity(self, local_batch: int, mesh: Mesh) -> int:
        """Per-bucket step width T of the elastic path: how many elements
        ONE bucket can absorb per global batch. A function of the GLOBAL
        batch and the bucket count only — deliberately independent of the
        device count, so the per-bucket computation (and therefore every
        dup verdict) is bit-identical across mesh sizes (§4.4)."""
        g = local_batch * self.n_shards(mesh)
        return max(8, math.ceil(g / self.n_buckets * self.capacity_factor))

    def step_width(self, local_batch: int, mesh: Mesh) -> int:
        """Owner-side compacted step width T' of the pipelined static path
        (§4.5): received keys are valid-prefix windows by construction, so
        the owner can pack them to ``local_batch`` expected elements plus an
        8-sigma Poisson margin instead of running the step at the flat
        ``n_shards * capacity`` dispatch width. Only used for variants whose
        decision consumes no per-lane randomness (``spec.draw is None``) —
        a width change re-indexes every rng draw for the others. Never wider
        than the flat width (capacity_factor < 1 keeps the flat layout)."""
        flat = self.n_shards(mesh) * self.capacity(local_batch, mesh)
        t = local_batch + max(64, math.ceil(8.0 * math.sqrt(local_batch)))
        return min(flat, max(8, -(-t // 8) * 8))


class ShardedDedup:
    """Mesh-wide dedup service. State lives sharded over ``mesh_axes``."""

    def __init__(self, scfg: ShardedDedupConfig, mesh: Mesh):
        self.scfg = scfg
        self.mesh = mesh
        self.n_shards = scfg.n_shards(mesh)
        if scfg.elastic:
            if scfg.n_buckets % self.n_shards:
                raise ValueError(
                    f"rebalance_buckets {scfg.n_buckets} must divide by the "
                    f"mesh's shard count {self.n_shards} (DESIGN §4.4)")
            self.b_r = scfg.n_buckets // self.n_shards   # bucket slots/shard
            # per-BUCKET sub-filter: aggregate memory over all buckets
            self.local_cfg = dataclasses.replace(
                scfg.base, shards=scfg.n_buckets).validate()
        else:
            self.b_r = 0
            # per-shard filter: aggregate memory divided across shards
            self.local_cfg = dataclasses.replace(
                scfg.base, shards=self.n_shards).validate()
        self._step = make_batched_step(self.local_cfg)
        self.axis = scfg.mesh_axes
        # owner-side step compaction (§4.5) is exact only when the decision
        # rule consumes no per-lane randomness — the rng stream is indexed
        # by lane, so ANY width change re-draws every lane
        self._compactable = get_spec(scfg.base.variant).draw is None
        # jitted callables are built once per (kind, local_batch) and reused —
        # same compile-cache discipline as the single-device engine (§3.5)
        self._step_fns: Dict[int, jax.stages.Wrapped] = {}
        self._stream_fns: Dict[Tuple[int, bool], jax.stages.Wrapped] = {}

    def _state_template(self) -> FilterState:
        """Structure-only FilterState matching what this service carries —
        including the swbf window ring (DESIGN §3.7) and the elastic router
        table (§4.4), whose leaves need PartitionSpecs like every other
        state field."""
        ring = (WindowRing(0, 0)
                if self.local_cfg.variant == "swbf" else None)
        router = RouterState(0, 0) if self.scfg.elastic else None
        return FilterState(0, 0, 0, 0, ring, router)

    # -------------------------------------------------------------- //
    def init(self, seed: int | None = None,
             event_capacity: int | None = None) -> FilterState:
        """Filter state with a leading shard axis, sharded over mesh_axes
        (elastic mode: a (n_shards, n_buckets/n_shards) grid of bucket
        sub-filters plus the replicated router table, §4.4).

        For swbf, each ring slot must absorb one step's WHOLE dispatch:
        statically routed, that is the per-shard flat buffer (n_shards ·
        capacity elements); elastically, the per-bucket step width
        (``bucket_capacity``). The default sizes the ring for
        ``run_stream`` / ``make_step(base.batch_size // n_shards)``;
        driving ``make_step`` with a LARGER local batch needs a matching
        ``event_capacity`` here."""
        local_batch = max(1, self.scfg.base.batch_size // self.n_shards)
        if self.scfg.elastic:
            return self._init_elastic(seed, event_capacity, local_batch)
        kw = {}
        if self.local_cfg.variant == "swbf":
            if event_capacity is None:
                # pipelined + compacted (§4.5): the step never runs wider
                # than the compacted width, so each ring slot only has to
                # absorb that many insertions — the ring (and every
                # ring-width sort/scatter per batch) shrinks with it
                if self.scfg.pipeline and self._compactable:
                    event_capacity = self.scfg.step_width(
                        local_batch, self.mesh)
                else:
                    event_capacity = (self.n_shards
                                      * self.scfg.capacity(local_batch,
                                                           self.mesh))
            kw["event_capacity"] = event_capacity

        def build():
            base = init_state(self.local_cfg, seed, **kw)

            def stack(x):
                return jnp.broadcast_to(x[None], (self.n_shards, *x.shape))

            return FilterState(
                bits=stack(base.bits),
                position=jnp.ones((self.n_shards,), jnp.int32),
                load=stack(base.load),
                rng=jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
                    base.rng, jnp.arange(self.n_shards)),
                ring=jax.tree.map(stack, base.ring),   # swbf ring (§3.7)
            )

        return self._build_sharded(build)

    def _build_sharded(self, build) -> FilterState:
        """Run the state builder under ``jax.jit`` with ``out_shardings``:
        each device materialises only its own shard of the stacked state —
        nothing filter-sized is ever built on one device and then split.
        Leaves with a leading shard axis shard over ``mesh_axes``; the
        elastic router table is replicated."""
        shaped = jax.eval_shape(build)

        def spec(x):
            return NamedSharding(self.mesh,
                                 P(self.axis, *([None] * (x.ndim - 1))))

        shardings = jax.tree.map(spec, shaped._replace(router=None))
        if shaped.router is not None:
            shardings = shardings._replace(router=jax.tree.map(
                lambda _: NamedSharding(self.mesh, P()), shaped.router))
        return jax.jit(build, out_shardings=shardings)()

    def _init_elastic(self, seed, event_capacity, local_batch) -> FilterState:
        """Elastic state (§4.4): leaves carry (n_shards, b_r, ...) — one
        self-contained sub-filter per bucket SLOT, the canonical block
        assignment placing bucket ``g`` in slot ``(g // b_r, g % b_r)``.
        Each bucket's rng is folded on its BUCKET id (not its shard), so the
        randomness stream travels with the bucket through re-partitions.
        The replicated router table rides as ``state.router``."""
        n, b_r, nb = self.n_shards, self.b_r, self.scfg.n_buckets
        kw = {}
        if self.local_cfg.variant == "swbf":
            if event_capacity is None:
                event_capacity = self.scfg.bucket_capacity(
                    local_batch, self.mesh)
            kw["event_capacity"] = event_capacity

        def build():
            base = init_state(self.local_cfg, seed, **kw)

            def stack(x):
                return jnp.broadcast_to(x[None, None], (n, b_r, *x.shape))

            bucket_ids = jnp.arange(nb, dtype=jnp.int32).reshape(n, b_r)
            return FilterState(
                bits=stack(base.bits),
                position=jnp.ones((n, b_r), jnp.int32),
                load=stack(base.load),
                rng=jax.vmap(jax.vmap(jax.random.fold_in, in_axes=(None, 0)),
                             in_axes=(None, 0))(base.rng, bucket_ids),
                ring=jax.tree.map(stack, base.ring),
                router=init_router(nb, n),
            )

        return self._build_sharded(build)

    # -------------------------------------------------------------- //
    def _local_fn(self, cap: int):
        """Per-device body: route -> all_to_all -> local batched step ->
        verdicts home. ``keys``/``valid`` are this device's slice; state
        fields carry leading dim 1 (this device's shard)."""
        n_shards, step = self.n_shards, self._step
        seed = self.local_cfg.seed
        all_axes = self.scfg.mesh_axes

        def local_fn(state: FilterState, keys: jnp.ndarray,
                     valid: jnp.ndarray):
            state = jax.tree.map(lambda x: x[0], state)
            owner = route_hash(keys, n_shards, seed)            # (b,)
            onehot = (valid[:, None] &
                      (owner[:, None] ==
                       jnp.arange(n_shards, dtype=jnp.int32)[None, :]))
            pos_in = jnp.cumsum(onehot, axis=0) - 1              # (b, S)
            my_pos = jnp.take_along_axis(
                pos_in, owner[:, None], axis=1)[:, 0]            # (b,)
            keep = valid & (my_pos < cap)
            overflow = jnp.sum(valid & ~keep)
            # dispatch buffers (S, C)
            send_keys = jnp.zeros((n_shards, cap), jnp.uint32)
            send_valid = jnp.zeros((n_shards, cap), bool)
            o = jnp.where(keep, owner, n_shards)                 # drop overflow
            p = jnp.where(keep, my_pos, 0)
            send_keys = send_keys.at[o, p].set(keys, mode="drop")
            send_valid = send_valid.at[o, p].set(True, mode="drop")
            # exchange: rows become per-source buffers for my shard
            recv_keys = jax.lax.all_to_all(
                send_keys, all_axes, split_axis=0, concat_axis=0, tiled=True)
            recv_valid = jax.lax.all_to_all(
                send_valid, all_axes, split_axis=0, concat_axis=0, tiled=True)
            # local dedup over everything I own this step
            flat_keys = recv_keys.reshape(-1)
            flat_valid = recv_valid.reshape(-1)
            state, res = step(state, flat_keys, flat_valid)
            dup_buf = res.dup.reshape(n_shards, cap)
            # verdicts home
            back = jax.lax.all_to_all(
                dup_buf, all_axes, split_axis=0, concat_axis=0, tiled=True)
            dup = back[o.clip(0, n_shards - 1), p] & keep        # overflow -> distinct
            state = jax.tree.map(lambda x: x[None], state)
            return state, dup, overflow[None].astype(jnp.int32)

        return local_fn

    # ------------------------------------------------- elastic path (§4.4) //
    def _axis_index(self):
        """Linearized device index over the flattened mesh axes — the same
        linearization ``all_to_all``/``ppermute`` use for tuple axis names."""
        idx = jnp.int32(0)
        for a in self.scfg.mesh_axes:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    @staticmethod
    def _slot_tables(assign: jnp.ndarray, n_shards: int, b_r: int):
        """Derive the two routing views of a bucket->shard assignment:
        ``slot_of[g]`` — bucket g's slot index within its owner (rank among
        same-owner buckets in bucket-id order), and ``slots[j, i]`` — the
        bucket id shard j holds in slot i. Both replicated; O(n_buckets^2)
        compares on a table of at most a few dozen entries."""
        nb = assign.shape[0]
        order = jnp.arange(nb, dtype=jnp.int32)
        before = ((assign[None, :] == assign[:, None])
                  & (order[None, :] < order[:, None]))
        slot_of = before.sum(axis=1, dtype=jnp.int32)
        slots = jnp.zeros((n_shards, b_r), jnp.int32).at[
            assign, slot_of].set(order)
        return slot_of, slots

    @staticmethod
    def _lpt_assign(bucket_load: jnp.ndarray, n_shards: int, b_r: int):
        """Greedy longest-processing-time re-pack: buckets in descending
        load order, each to the least-loaded shard with a free slot (every
        shard keeps EXACTLY b_r buckets — the state layout is a fixed
        (n_shards, b_r) grid). Pure function of the replicated load vector,
        stable sort + lowest-index argmin tie-breaks: every device computes
        the identical table."""
        nb = bucket_load.shape[0]
        order_desc = jnp.argsort(-bucket_load).astype(jnp.int32)

        def body(carry, g):
            sload, scount = carry
            cost = jnp.where(scount >= b_r, _INT32_MAX, sload)
            j = jnp.argmin(cost).astype(jnp.int32)
            return ((sload.at[j].add(bucket_load[g]), scount.at[j].add(1)),
                    j)

        zeros = jnp.zeros((n_shards,), jnp.int32)
        _, owners = jax.lax.scan(body, (zeros, zeros), order_desc)
        return jnp.zeros((nb,), jnp.int32).at[order_desc].set(owners)

    def _elastic_local_fn(self, local_batch: int):
        """Per-device body of the elastic path: range-route -> per-bucket
        dispatch -> tag-ordered compaction -> one batched step per local
        bucket slot -> verdicts home -> load monitor (+ cond-gated bucket
        permute). The per-bucket work stream (keys in stream order, widths,
        rng) is invariant to bucket placement AND device count — the §4.4
        bit-parity contract."""
        n_shards, b_r, nb = self.n_shards, self.b_r, self.scfg.n_buckets
        step = self._step
        t_width = self.scfg.bucket_capacity(local_batch, self.mesh)
        cap = -(-t_width // n_shards)        # per (bucket, source) window
        all_axes = self.scfg.mesh_axes
        monitor = self._monitor_fn()
        rows_e = jnp.arange(b_r, dtype=jnp.int32)[:, None]
        order = jnp.arange(nb, dtype=jnp.int32)

        def local_fn(state: FilterState, keys: jnp.ndarray,
                     valid: jnp.ndarray):
            router = state.router
            bstate = jax.tree.map(lambda x: x[0], state._replace(router=None))
            assign = router.assign                           # (nb,) replicated
            slot_of, slots = self._slot_tables(assign, n_shards, b_r)
            me = self._axis_index()
            b = keys.shape[0]

            # ---- route + per-(bucket, source) compaction ---------------- //
            bucket = range_bucket(keys, nb)                  # (b,)
            onehot = valid[:, None] & (bucket[:, None] == order[None, :])
            pos_in = jnp.cumsum(onehot, axis=0) - 1          # (b, nb)
            my_pos = jnp.take_along_axis(
                pos_in, bucket[:, None], axis=1)[:, 0]       # (b,)
            keep = valid & (my_pos < cap)
            src_overflow = jnp.sum(valid & ~keep)
            dest = assign[bucket]
            tag = me * b + jnp.arange(b, dtype=jnp.int32)    # global batch pos
            o = jnp.where(keep, dest, n_shards)              # drop overflow
            sl = jnp.where(keep, slot_of[bucket], 0)
            p = jnp.where(keep, my_pos, 0)
            send_keys = jnp.zeros((n_shards, b_r, cap), jnp.uint32
                                  ).at[o, sl, p].set(keys, mode="drop")
            send_tags = jnp.full((n_shards, b_r, cap), _INT32_MAX, jnp.int32
                                 ).at[o, sl, p].set(tag, mode="drop")
            send_valid = jnp.zeros((n_shards, b_r, cap), bool
                                   ).at[o, sl, p].set(True, mode="drop")

            def a2a(x):
                flat = x.reshape(n_shards, -1)
                out = jax.lax.all_to_all(flat, all_axes, split_axis=0,
                                         concat_axis=0, tiled=True)
                return out.reshape(n_shards, b_r, cap)

            recv_keys, recv_tags, recv_valid = (
                a2a(send_keys), a2a(send_tags), a2a(send_valid))

            # ---- stream-order compaction to the fixed step width T ------ //
            # (b_r, E): slot-major view of everything I own this step
            rk = recv_keys.transpose(1, 0, 2).reshape(b_r, -1)
            rt = jnp.where(recv_valid, recv_tags, _INT32_MAX
                           ).transpose(1, 0, 2).reshape(b_r, -1)
            rv = recv_valid.transpose(1, 0, 2).reshape(b_r, -1)
            stags = jnp.sort(rt, axis=-1)                    # value-free sort
            rank = jax.vmap(
                lambda s, t: jnp.searchsorted(s, t, side="left"))(
                    stags, rt).astype(jnp.int32)
            ok = rv & (rank < t_width)
            rank_overflow = jnp.sum(rv & ~ok)
            tgt = jnp.where(ok, rank, t_width)
            ck = jnp.zeros((b_r, t_width), jnp.uint32
                           ).at[rows_e, tgt].set(rk, mode="drop")
            n_val = jnp.minimum(jnp.sum(ok, axis=-1), t_width)
            cvalid = (jnp.arange(t_width, dtype=jnp.int32)[None, :]
                      < n_val[:, None])

            # ---- one batched step per local bucket slot ----------------- //
            # lax.scan over the stacked slot axis, not a python unroll:
            # buckets are independent and homogeneous, so ONE compiled body
            # serves every slot — trace/compile size stays O(1) in b_r
            # (the 1-device oracle carries b_r == n_buckets)
            def slot_body(_, xs):
                st_i, kk, vv = xs
                st_i, res = step(st_i, kk, vv)
                return _, (st_i, res.dup)

            _, (new_bstate, dup_c) = jax.lax.scan(
                slot_body, 0, (bstate, ck, cvalid))          # dup_c (b_r, T)

            # ---- verdicts home ------------------------------------------ //
            dup_recv = (jnp.take_along_axis(
                dup_c, jnp.minimum(rank, t_width - 1), axis=-1) & ok)
            back = dup_recv.reshape(b_r, n_shards, cap).transpose(1, 0, 2)
            back = jax.lax.all_to_all(
                back.reshape(n_shards, -1), all_axes, split_axis=0,
                concat_axis=0, tiled=True).reshape(n_shards, b_r, cap)
            dup = back[o.clip(0, n_shards - 1), sl, p] & keep

            # ---- load monitor + cond-gated re-partition (§4.4) ---------- //
            new_bstate, router = monitor(new_bstate, router, me)

            out = jax.tree.map(lambda x: x[None], new_bstate)
            out = out._replace(router=router)
            overflow = (src_overflow + rank_overflow)[None].astype(jnp.int32)
            return out, dup, overflow

        return local_fn

    def _monitor_fn(self):
        """The per-batch load monitor + cond-gated bucket re-partition
        (§4.4), shared verbatim by the serial elastic body and the pipelined
        consume stage: (bucket-slot state, router, device index) ->
        (possibly permuted state, updated router). A no-op when
        ``rebalance_threshold`` is 0 (monitoring off)."""
        n_shards, b_r, nb = self.n_shards, self.b_r, self.scfg.n_buckets
        all_axes = self.scfg.mesh_axes
        threshold = float(self.scfg.base.rebalance_threshold)

        def monitor(new_bstate, router: RouterState, me):
            if threshold <= 0.0:
                return new_bstate, router
            assign = router.assign
            _, slots = self._slot_tables(assign, n_shards, b_r)
            my_ids = slots[me]                               # (b_r,)
            slot_load = new_bstate.load.sum(axis=-1)         # (b_r,)
            contrib = jnp.zeros((nb,), jnp.int32).at[my_ids].set(slot_load)
            bucket_load = jax.lax.psum(contrib, all_axes)
            shard_load = jnp.zeros((n_shards,), jnp.int32
                                   ).at[assign].add(bucket_load)
            total = shard_load.sum()
            ratio = (shard_load.max().astype(jnp.float32) * n_shards
                     / jnp.maximum(total, 1).astype(jnp.float32))
            repacked = self._lpt_assign(bucket_load, n_shards, b_r)
            # fire only when the re-pack STRICTLY lowers the max shard
            # load — a skew the packing cannot improve (e.g. one bucket
            # per shard, where any re-pack is a pure permutation) must
            # not permute state in place every batch
            repacked_load = jnp.zeros((n_shards,), jnp.int32
                                      ).at[repacked].add(bucket_load)
            trigger = ((ratio > threshold) & (total > 0)
                       & (repacked_load.max() < shard_load.max()))
            new_assign = jnp.where(trigger, repacked, assign)
            _, new_slots = self._slot_tables(new_assign, n_shards, b_r)
            want = new_slots[me]                             # (b_r,)
            new_bstate = jax.lax.cond(
                trigger,
                lambda t: rebalance_collect(t, my_ids, want, all_axes,
                                            n_shards),
                lambda t: t,
                new_bstate)
            router = RouterState(
                assign=new_assign,
                n_rebalances=router.n_rebalances + trigger.astype(jnp.int32))
            return new_bstate, router

        return monitor

    # --------------------------------------------- pipelined path (§4.5) //
    def _static_pipe_fns(self, local_batch: int):
        """Dispatch/consume split of the static body for the double-buffered
        scan (§4.5). ``dispatch`` routes a batch and starts its all_to_all;
        ``consume`` runs the (possibly compacted) batched step on a
        previously dispatched batch and routes the verdicts home. The
        receive-side valid mask is NOT shipped: every (source, dest) window
        is a valid-prefix by construction (positions are cumsum ranks), so
        per-source COUNTS reconstruct it exactly — one all_to_all fewer per
        batch than the serial body, bit-identical verdicts."""
        n_shards, step = self.n_shards, self._step
        seed = self.local_cfg.seed
        all_axes = self.scfg.mesh_axes
        cap = self.scfg.capacity(local_batch, self.mesh)
        flat = n_shards * cap
        t_width = (self.scfg.step_width(local_batch, self.mesh)
                   if self._compactable else flat)

        def dispatch(state: FilterState, keys: jnp.ndarray,
                     valid: jnp.ndarray) -> InFlight:
            del state                        # static routing reads no state
            owner = route_hash(keys, n_shards, seed)
            onehot = (valid[:, None] &
                      (owner[:, None] ==
                       jnp.arange(n_shards, dtype=jnp.int32)[None, :]))
            pos_in = jnp.cumsum(onehot, axis=0) - 1
            my_pos = jnp.take_along_axis(
                pos_in, owner[:, None], axis=1)[:, 0]
            keep = valid & (my_pos < cap)
            overflow = jnp.sum(valid & ~keep)
            o = jnp.where(keep, owner, n_shards)
            p = jnp.where(keep, my_pos, 0)
            send_keys = jnp.zeros((n_shards, cap), jnp.uint32
                                  ).at[o, p].set(keys, mode="drop")
            send_cnt = jnp.sum(onehot & keep[:, None], axis=0,
                               dtype=jnp.int32)                  # (S,)
            recv_keys = jax.lax.all_to_all(
                send_keys, all_axes, split_axis=0, concat_axis=0, tiled=True)
            recv_cnt = jax.lax.all_to_all(
                send_cnt, all_axes, split_axis=0, concat_axis=0, tiled=True)
            return InFlight(recv_keys[None], recv_cnt[None], o[None], None,
                            p[None], keep[None],
                            overflow[None].astype(jnp.int32))

        def consume(state: FilterState, fl: InFlight):
            state = jax.tree.map(lambda x: x[0], state)
            rk, cnt = fl.keys[0], fl.cnt[0]
            lanes = jnp.arange(cap, dtype=jnp.int32)[None, :]
            vmask = lanes < cnt[:, None]                     # (S, C)
            if t_width < flat:
                # owner-side compaction: rank = lanes before me, globally
                offs = jnp.cumsum(cnt) - cnt                 # exclusive
                rankm = offs[:, None] + lanes                # (S, C)
                ok = vmask & (rankm < t_width)
                rank_overflow = jnp.sum(vmask & ~ok)
                tgt = jnp.where(ok, rankm, t_width)
                ck = jnp.zeros((t_width,), jnp.uint32
                               ).at[tgt.reshape(-1)].set(
                                   rk.reshape(-1), mode="drop")
                cvalid = (jnp.arange(t_width, dtype=jnp.int32)
                          < jnp.minimum(cnt.sum(), t_width))
                state, res = step(state, ck, cvalid)
                dup_buf = res.dup[jnp.minimum(rankm, t_width - 1)] & ok
            else:
                rank_overflow = jnp.int32(0)
                state, res = step(state, rk.reshape(-1), vmask.reshape(-1))
                dup_buf = res.dup.reshape(n_shards, cap)
            back = jax.lax.all_to_all(
                dup_buf, all_axes, split_axis=0, concat_axis=0, tiled=True)
            dup = back[fl.o[0].clip(0, n_shards - 1), fl.p[0]] & fl.keep[0]
            state = jax.tree.map(lambda x: x[None], state)
            ovf = fl.ovf + rank_overflow.astype(jnp.int32)
            return state, dup, ovf

        return dispatch, consume

    def _elastic_pipe_fns(self, local_batch: int):
        """Dispatch/consume split of the elastic body (§4.4 + §4.5). The
        serial body's per-lane TAG buffer, its all_to_all, the valid-mask
        all_to_all, and the per-slot tag SORT all disappear: tags are
        source-major with in-source arrival order by construction, so a
        valid lane's compaction rank is exactly (valid lanes from earlier
        sources) + (its own prefix position) — an exclusive cumsum of the
        shipped per-(source, slot) counts. Same step width T, same rng
        threading: bit-identical to the serial elastic body for EVERY
        variant, and therefore still device-count-invariant."""
        n_shards, b_r, nb = self.n_shards, self.b_r, self.scfg.n_buckets
        step = self._step
        t_width = self.scfg.bucket_capacity(local_batch, self.mesh)
        cap = -(-t_width // n_shards)        # per (bucket, source) window
        all_axes = self.scfg.mesh_axes
        monitor = self._monitor_fn()
        order = jnp.arange(nb, dtype=jnp.int32)
        rows3 = jnp.arange(b_r, dtype=jnp.int32)[None, :, None]

        def a2a(x):
            flat = x.reshape(n_shards, -1)
            out = jax.lax.all_to_all(flat, all_axes, split_axis=0,
                                     concat_axis=0, tiled=True)
            return out.reshape(x.shape)

        def dispatch(state: FilterState, keys: jnp.ndarray,
                     valid: jnp.ndarray) -> InFlight:
            assign = state.router.assign                 # (nb,) replicated
            slot_of, _ = self._slot_tables(assign, n_shards, b_r)
            bucket = range_bucket(keys, nb)
            onehot = valid[:, None] & (bucket[:, None] == order[None, :])
            pos_in = jnp.cumsum(onehot, axis=0) - 1
            my_pos = jnp.take_along_axis(
                pos_in, bucket[:, None], axis=1)[:, 0]
            keep = valid & (my_pos < cap)
            src_overflow = jnp.sum(valid & ~keep)
            dest = assign[bucket]
            o = jnp.where(keep, dest, n_shards)
            sl = jnp.where(keep, slot_of[bucket], 0)
            p = jnp.where(keep, my_pos, 0)
            send_keys = jnp.zeros((n_shards, b_r, cap), jnp.uint32
                                  ).at[o, sl, p].set(keys, mode="drop")
            cnt_bucket = jnp.sum(onehot & keep[:, None], axis=0,
                                 dtype=jnp.int32)            # (nb,)
            send_cnt = jnp.zeros((n_shards, b_r), jnp.int32
                                 ).at[assign, slot_of].set(cnt_bucket)
            recv_keys = a2a(send_keys)
            recv_cnt = a2a(send_cnt)
            return InFlight(recv_keys[None], recv_cnt[None], o[None],
                            sl[None], p[None], keep[None],
                            src_overflow[None].astype(jnp.int32))

        def consume(state: FilterState, fl: InFlight):
            router = state.router
            bstate = jax.tree.map(lambda x: x[0], state._replace(router=None))
            me = self._axis_index()
            rk, cnt = fl.keys[0], fl.cnt[0]          # (S, b_r, C) / (S, b_r)
            lanes = jnp.arange(cap, dtype=jnp.int32)
            vmask = lanes[None, None, :] < cnt[..., None]
            offs = jnp.cumsum(cnt, axis=0) - cnt     # exclusive over sources
            rankm = offs[..., None] + lanes[None, None, :]
            ok = vmask & (rankm < t_width)
            rank_overflow = jnp.sum(vmask & ~ok)
            tgt = jnp.where(ok, rankm, t_width)
            ck = jnp.zeros((b_r, t_width), jnp.uint32
                           ).at[jnp.broadcast_to(rows3, tgt.shape), tgt
                                ].set(rk, mode="drop")
            n_val = jnp.minimum(cnt.sum(axis=0), t_width)    # (b_r,)
            cvalid = (jnp.arange(t_width, dtype=jnp.int32)[None, :]
                      < n_val[:, None])

            def slot_body(_, xs):
                st_i, kk, vv = xs
                st_i, res = step(st_i, kk, vv)
                return _, (st_i, res.dup)

            _, (new_bstate, dup_c) = jax.lax.scan(
                slot_body, 0, (bstate, ck, cvalid))          # dup_c (b_r, T)
            dup_sel = (dup_c[jnp.broadcast_to(rows3, rankm.shape),
                             jnp.minimum(rankm, t_width - 1)] & ok)
            back = a2a(dup_sel)                              # (S, b_r, C)
            dup = (back[fl.o[0].clip(0, n_shards - 1), fl.sl[0], fl.p[0]]
                   & fl.keep[0])
            new_bstate, router = monitor(new_bstate, router, me)
            out = jax.tree.map(lambda x: x[None], new_bstate)
            out = out._replace(router=router)
            ovf = fl.ovf + rank_overflow.astype(jnp.int32)
            return out, dup, ovf

        return dispatch, consume

    def _pipe_shard_mapped(self, local_batch: int):
        """Shard-mapped prologue / body / epilogue of the pipelined stream
        (§4.5). The scan carry is (FilterState, InFlight): iteration t first
        CONSUMES batch t-1 (step + verdict return + elastic monitor), then
        DISPATCHES batch t with the post-monitor router — the same
        state-update order as the serial scan, so verdicts are bit-identical
        pipelined-on vs pipelined-off."""
        t = self._state_template()

        def sub(subtree, spec):
            return jax.tree.map(lambda _: spec, subtree)

        state_spec = FilterState(
            bits=P(self.axis), position=P(self.axis), load=P(self.axis),
            rng=P(self.axis), ring=sub(t.ring, P(self.axis)),
            router=sub(t.router, P()))
        batch_spec = P(self.scfg.batch_axes)
        if self.scfg.elastic:
            dispatch, consume = self._elastic_pipe_fns(local_batch)
        else:
            dispatch, consume = self._static_pipe_fns(local_batch)
        fl_spec = InFlight(
            keys=P(self.axis), cnt=P(self.axis), o=P(self.axis),
            sl=(P(self.axis) if self.scfg.elastic else None),
            p=P(self.axis), keep=P(self.axis), ovf=P(self.axis))

        def prologue_fn(state, keys, valid):
            return dispatch(state, keys, valid)

        def body_fn(state, fl, keys, valid):
            state, dup, ovf = consume(state, fl)
            fl = dispatch(state, keys, valid)
            return state, fl, dup, ovf

        def epilogue_fn(state, fl):
            return consume(state, fl)

        prologue = compat.shard_map(
            prologue_fn, mesh=self.mesh,
            in_specs=(state_spec, batch_spec, batch_spec),
            out_specs=fl_spec, check_vma=False)
        body = compat.shard_map(
            body_fn, mesh=self.mesh,
            in_specs=(state_spec, fl_spec, batch_spec, batch_spec),
            out_specs=(state_spec, fl_spec, batch_spec, P(self.axis)),
            check_vma=False)
        epilogue = compat.shard_map(
            epilogue_fn, mesh=self.mesh,
            in_specs=(state_spec, fl_spec),
            out_specs=(state_spec, batch_spec, P(self.axis)),
            check_vma=False)
        return prologue, body, epilogue

    def _shard_mapped(self, local_batch: int):
        """The shard-mapped (state, keys, valid) -> (state, dup, ovf) body;
        ``keys`` is the *global* batch sharded over batch_axes, state carries
        the leading shard axis sharded over mesh_axes (the elastic router
        table is replicated — every device must route identically)."""
        t = self._state_template()

        def sub(subtree, spec):
            return jax.tree.map(lambda _: spec, subtree)

        state_spec = FilterState(
            bits=P(self.axis), position=P(self.axis), load=P(self.axis),
            rng=P(self.axis), ring=sub(t.ring, P(self.axis)),
            router=sub(t.router, P()))
        batch_spec = P(self.scfg.batch_axes)
        if self.scfg.elastic:
            body = self._elastic_local_fn(local_batch)
        else:
            body = self._local_fn(self.scfg.capacity(local_batch, self.mesh))
        return compat.shard_map(
            body, mesh=self.mesh,
            in_specs=(state_spec, batch_spec, batch_spec),
            out_specs=(state_spec, batch_spec, P(self.axis)),
            check_vma=False)

    def _pipe_fused_shard_mapped(self, local_batch: int):
        """Single-batch dispatch+consume of the pipelined protocol (§4.5) —
        the ``make_step`` entry point when ``pipeline=True``, so per-batch
        stepping uses the same count-based dispatch, compacted step width,
        and (for swbf) ring sizing as the double-buffered stream, and the
        two entry points stay bit-identical on a shared ``init()``."""
        t = self._state_template()

        def sub(subtree, spec):
            return jax.tree.map(lambda _: spec, subtree)

        state_spec = FilterState(
            bits=P(self.axis), position=P(self.axis), load=P(self.axis),
            rng=P(self.axis), ring=sub(t.ring, P(self.axis)),
            router=sub(t.router, P()))
        batch_spec = P(self.scfg.batch_axes)
        if self.scfg.elastic:
            dispatch, consume = self._elastic_pipe_fns(local_batch)
        else:
            dispatch, consume = self._static_pipe_fns(local_batch)

        def fused(state, keys, valid):
            fl = dispatch(state, keys, valid)
            return consume(state, fl)

        return compat.shard_map(
            fused, mesh=self.mesh,
            in_specs=(state_spec, batch_spec, batch_spec),
            out_specs=(state_spec, batch_spec, P(self.axis)),
            check_vma=False)

    # -------------------------------------------------------------- //
    def make_step(self, local_batch: int):
        """Returns a jitted (state, keys) -> (state, dup, overflow) fn for
        one global batch of ``local_batch * n_shards`` keys (all valid)."""
        if local_batch not in self._step_fns:
            smapped = (self._pipe_fused_shard_mapped(local_batch)
                       if self.scfg.pipeline
                       else self._shard_mapped(local_batch))

            def step(state: FilterState, keys: jnp.ndarray):
                valid = jnp.ones(keys.shape, bool)
                return smapped(state, keys, valid)

            self._step_fns[local_batch] = jax.jit(step)
        return self._step_fns[local_batch]

    # -------------------------------------------------------------- //
    def _make_stream(self, local_batch: int):
        """One jitted scan over batches of the shard-mapped body, the sharded
        state donated (aliased in place across the whole stream) — the
        sharded mirror of the single-device ``run_stream`` (§3.5).

        With ``pipeline=True`` (default) the scan is double-buffered
        (§4.5): a prologue dispatches batch 0 (route + all_to_all, no state
        touched), each scan iteration consumes the in-flight batch and
        dispatches the next one, and an epilogue consumes the final batch —
        so batch t+1's routing and key exchange are issued while batch t's
        step is still outstanding, giving the XLA scheduler an async
        collective to overlap with compute. Verdicts are bit-identical to
        the serial scan; the verdict row for batch t is simply produced one
        iteration later."""
        key = (local_batch, bool(self.scfg.pipeline))
        if key not in self._stream_fns:
            if self.scfg.pipeline:
                prologue, body_sm, epilogue = (
                    self._pipe_shard_mapped(local_batch))

                def stream(state: FilterState, kb: jnp.ndarray,
                           vb: jnp.ndarray):
                    fl0 = prologue(state, kb[0], vb[0])

                    def body(carry, xs):
                        st, fl = carry
                        kk, vv = xs
                        st, fl, dup, ovf = body_sm(st, fl, kk, vv)
                        return (st, fl), (dup, ovf)

                    (state, fl_last), (dups, ovfs) = jax.lax.scan(
                        body, (state, fl0), (kb[1:], vb[1:]))
                    state, dup_last, ovf_last = epilogue(state, fl_last)
                    dups = jnp.concatenate([dups, dup_last[None]], axis=0)
                    ovfs = jnp.concatenate([ovfs, ovf_last[None]], axis=0)
                    return state, dups, ovfs
            else:
                smapped = self._shard_mapped(local_batch)

                def stream(state: FilterState, kb: jnp.ndarray,
                           vb: jnp.ndarray):
                    def body(st, xs):
                        kk, vv = xs
                        st, dup, ovf = smapped(st, kk, vv)
                        return st, (dup, ovf)

                    state, (dups, ovfs) = jax.lax.scan(body, state, (kb, vb))
                    return state, dups, ovfs

            self._stream_fns[key] = jax.jit(stream, donate_argnums=0)
        return self._stream_fns[key]

    def run_stream(self, state: FilterState, keys: jnp.ndarray
                   ) -> Tuple[FilterState, jnp.ndarray, jnp.ndarray]:
        """Whole (N,) stream in ONE dispatch: pad the tail with invalid
        lanes, reshape to (n_batches, global_batch), scan the shard-mapped
        step. Returns (state, per-element dup (N,), per-batch-per-shard
        overflow (n_batches, n_shards) int32 — a device array; feed it to
        ``StreamMetrics.update(overflow=...)`` to accumulate without a host
        sync).

        The input ``state`` is donated — use the returned state afterwards,
        never the argument (same contract as ``Dedup.run_stream``)."""
        b = self.scfg.base.batch_size
        if b % self.n_shards:
            raise ValueError(
                f"batch_size {b} must divide by n_shards {self.n_shards}")
        n = keys.shape[0]
        n_pad = (-n) % b
        keys_p = jnp.pad(keys.astype(jnp.uint32), (0, n_pad))
        valid = jnp.pad(jnp.ones((n,), bool), (0, n_pad))
        kb = keys_p.reshape(-1, b)
        vb = valid.reshape(-1, b)
        stream = self._make_stream(b // self.n_shards)
        state, dups, ovfs = stream(state, kb, vb)
        return state, dups.reshape(-1)[:n], ovfs

    def run_tenant_stream(self, state: FilterState, keys: jnp.ndarray,
                          tenant: jnp.ndarray
                          ) -> Tuple[FilterState, jnp.ndarray, jnp.ndarray]:
        """Sharded TENANT FLEET (DESIGN §4.6): the elastic path with one
        router bucket per tenant. The tenant id rides the top log2(T) bits
        of the tenant-tagged key (``core.fleet.tenant_tagged_keys``), so
        ``range_bucket(tagged, T)`` IS the tenant id — every bucket is one
        tenant's self-contained sub-filter (its own bits/position/load and
        a bucket(=tenant)-folded rng), the load-triggered LPT monitor
        (§4.4) rebalances TENANTS across shards wholesale, and verdicts are
        bit-identical across mesh sizes because the per-bucket step width
        is device-count-invariant. No new routing machinery: same scan,
        same ppermute ring, same checkpoint format.

        Requires ``rebalance_buckets == base.n_tenants`` (> 1) — that
        equality is what makes bucket identity equal tenant identity."""
        from ..core.fleet import tenant_tagged_keys
        t = self.scfg.base.n_tenants
        if t <= 1 or not self.scfg.elastic or self.scfg.n_buckets != t:
            raise ValueError(
                f"run_tenant_stream needs the elastic path with one bucket "
                f"per tenant: set rebalance_buckets == n_tenants (> 1); got "
                f"n_tenants={t}, rebalance_buckets={self.scfg.n_buckets} "
                f"(DESIGN §4.6)")
        tagged = tenant_tagged_keys(keys.astype(jnp.uint32),
                                    jnp.asarray(tenant, jnp.int32), t)
        return self.run_stream(state, tagged)

    def stream_cache_size(self) -> int:
        """Compiled specializations of the stream scan (one per distinct
        stream length) — the sharded no-recompile regression hook, mirroring
        ``Dedup.stream_cache_size``."""
        return sum(compat.jit_cache_size(fn)
                   for fn in self._stream_fns.values())
