"""Every jitted hot path, enumerated (DESIGN.md §6).

The lint engine is only as good as its coverage: ``iter_entry_points()``
builds the full matrix of compiled surfaces the serving/ingest system
actually dispatches — the templated step for each registered ``SketchSpec``
× layout × backend, the donated single-device stream scan, the sharded
serial/pipelined/rebalance streams, and the serving executor's padded
donated step — each at a small canonical config chosen so the lint
thresholds separate (filter well above every batch-event buffer) and the
whole sweep compiles in minutes on CPU.

Entry points are LAZY: enumerating the matrix touches no device and traces
nothing; each entry lowers/compiles only when a rule inspects it, and at
most once (``hlo_lint.Target`` caches). ``leaves()`` describes the donated
state leaves via ``jax.eval_shape`` where possible, so even the donation
rule's expectations cost no device work.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, FrozenSet, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.config import DedupConfig
from ..core.sketch import SKETCHES
from ..core.state import init_state
from ..compat import make_mesh

# canonical sweep sizes: small enough to compile fast, large enough that
# the filter (W words / s cells) sits well above every batch-event buffer
CANON_MEMORY_BITS = 1 << 20
CANON_BATCH = 256
STREAM_BATCHES = 4


@dataclasses.dataclass
class EntryPoint:
    """One jitted hot path. ``build`` lazily returns the ``jax.stages
    .Lowered``; ``leaves`` (donated entries) lazily returns the state-leaf
    spec ``[(label, shape, dtype)]`` the aliasing rule must find in the
    compiled alias table; ``retrace_probe`` (when set) executes the path
    twice and returns a list of problem strings for the no-retrace rule.
    ``extra`` carries rule thresholds (``filter_elems``, ``separable``)."""
    name: str
    tags: FrozenSet[str]
    cfg: Optional[DedupConfig]
    build: Callable[[], "jax.stages.Lowered"]
    leaves: Optional[Callable[[], List[Tuple[str, tuple, str]]]] = None
    retrace_probe: Optional[Callable[[], List[str]]] = None
    extra: Dict = dataclasses.field(default_factory=dict)


def _lazy(fn):
    """Memoize a zero-arg thunk (shared by build/leaves/probe closures)."""
    box: list = []

    def get():
        if not box:
            box.append(fn())
        return box[0]
    return get


def _leaf_spec(state) -> List[Tuple[str, tuple, str]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    return [(jax.tree_util.keystr(path), tuple(leaf.shape), str(leaf.dtype))
            for path, leaf in flat]


def _thresholds(cfg: DedupConfig) -> Dict:
    """filter_elems: the smallest per-row buffer that counts as "filter
    sized" (plane words / dense8 cells). separable: every batch-event
    buffer (B·k insert events, B·P sbf decrements) sits strictly below it,
    so the no-reduce rule cannot false-positive on event-sized reduces."""
    t = cfg.s_words if cfg.is_planes else cfg.s
    p = cfg.sbf_p_effective if cfg.variant == "sbf" else cfg.k
    events = cfg.batch_size * max(cfg.k, p)
    return {"filter_elems": t, "separable": events < t}


def _canon_cfg(variant: str, layout: str, backend: str = "jnp",
               **kw) -> DedupConfig:
    return DedupConfig.for_variant(
        variant, memory_bits=CANON_MEMORY_BITS, batch_size=CANON_BATCH,
        layout=layout, backend=backend, **kw)


def _shapes(cfg: DedupConfig):
    k = jax.ShapeDtypeStruct((cfg.batch_size,), jnp.uint32)
    v = jax.ShapeDtypeStruct((cfg.batch_size,), jnp.bool_)
    return k, v


def _demo_keys(cfg: DedupConfig, n: int) -> jnp.ndarray:
    return jnp.asarray(np.random.default_rng(0)
                       .integers(0, 1 << 20, n).astype(np.uint32))


# ---------------------------------------------------------------- factories


def step_entry(cfg: DedupConfig, *, name: Optional[str] = None) -> EntryPoint:
    """The batched step (``Dedup.process`` path) — NOT donated: interactive
    callers keep their argument state (DESIGN §3.5)."""
    cfg = cfg.validate()
    if name is None:
        dbg = "/debug-exact-load" if cfg.debug_exact_load else ""
        name = (f"step/{cfg.variant}/{cfg.effective_layout}/"
                f"{cfg.backend}{dbg}")

    def build():
        from ..core.batched import make_batched_step
        st = jax.eval_shape(functools.partial(init_state, cfg))
        return jax.jit(make_batched_step(cfg)).lower(st, *_shapes(cfg))

    return EntryPoint(
        name=name, tags=frozenset({"step", cfg.backend}), cfg=cfg,
        build=build, extra=_thresholds(cfg))


def stream_entry(cfg: DedupConfig, *, donate: bool = True,
                 probe: bool = False,
                 name: Optional[str] = None) -> EntryPoint:
    """The donated stream scan (``Dedup.run_stream``). ``donate=False``
    builds a deliberately-broken twin (state NOT donated) so the linter's
    own tests can watch ``state-donated-and-aliased`` fire."""
    cfg = cfg.validate()
    if name is None:
        name = (f"stream/{cfg.variant}/{cfg.effective_layout}/{cfg.backend}"
                + ("" if donate else "/no-donate"))
    ctx = _lazy(lambda: _stream_ctx(cfg, donate))

    def build():
        return ctx()["lowered"]

    def leaves():
        return ctx()["leaves"]

    def retrace():
        from ..core.engine import Dedup
        d = Dedup(cfg)
        keys = _demo_keys(cfg, STREAM_BATCHES * cfg.batch_size)
        st, _ = d.run_stream(d.init(), keys)
        first = d.stream_cache_size()
        problems = []
        if first != 1:
            problems.append(f"first run_stream compiled {first} "
                            f"specializations (expected 1)")
        st, _ = d.run_stream(d.init(), keys)
        if d.stream_cache_size() != first:
            problems.append("re-running the same-shape stream re-traced "
                            "the donated scan")
        return problems

    return EntryPoint(
        name=name,
        tags=frozenset({"stream", cfg.backend}
                       | ({"donated"} if donate else set())),
        cfg=cfg, build=build, leaves=leaves,
        retrace_probe=retrace if probe else None, extra=_thresholds(cfg))


def _stream_ctx(cfg: DedupConfig, donate: bool):
    from ..core.engine import Dedup
    d = Dedup(cfg)
    st = jax.eval_shape(functools.partial(init_state, cfg))
    kb = jax.ShapeDtypeStruct((STREAM_BATCHES, cfg.batch_size), jnp.uint32)
    vb = jax.ShapeDtypeStruct((STREAM_BATCHES, cfg.batch_size), jnp.bool_)
    fn = d._stream if donate else jax.jit(d._stream_impl)
    return {"lowered": fn.lower(st, kb, vb), "leaves": _leaf_spec(st)}


def sharded_stream_entry(*, pipeline: bool, rebalance_buckets: int = 0,
                         variant: str = "rlbsbf", probe: bool = False,
                         name: Optional[str] = None) -> EntryPoint:
    """The sharded donated stream (``ShardedDedup.run_stream``) on an
    in-process 1×1 mesh — serial, double-buffered pipelined (DESIGN §4.5),
    and elastic-rebalance (§4.4) bodies all sweep through the same scan."""
    mode = "elastic" if rebalance_buckets else "static"
    if name is None:
        name = (f"sharded-stream/{mode}/"
                f"{'pipelined' if pipeline else 'serial'}/{variant}")
    base = _canon_cfg(variant, "planes",
                      rebalance_buckets=rebalance_buckets)

    def make_sd():
        from ..dedup.sharded import ShardedDedup, ShardedDedupConfig
        mesh = make_mesh((1, 1), ("data", "model"))
        scfg = ShardedDedupConfig(base=base, pipeline=pipeline)
        return ShardedDedup(scfg, mesh)

    ctx = _lazy(lambda: _sharded_ctx(make_sd()))

    def build():
        return ctx()["lowered"]

    def leaves():
        return ctx()["leaves"]

    def retrace():
        sd = make_sd()
        keys = _demo_keys(base, STREAM_BATCHES * base.batch_size)
        st, _, _ = sd.run_stream(sd.init(), keys)
        first = sd.stream_cache_size()
        problems = []
        if first != 1:
            problems.append(f"first sharded run_stream compiled {first} "
                            f"specializations (expected 1)")
        st, _, _ = sd.run_stream(sd.init(), keys)
        if sd.stream_cache_size() != first:
            problems.append("re-running the same-shape sharded stream "
                            "re-traced the donated scan")
        return problems

    cfg_local = base   # threshold config: per-shard W on a 1-shard mesh
    return EntryPoint(
        name=name,
        tags=frozenset({"stream", "sharded", "donated", mode,
                        "pipelined" if pipeline else "serial"}),
        cfg=cfg_local, build=build, leaves=leaves,
        retrace_probe=retrace if probe else None,
        extra=_thresholds(cfg_local))


def _sharded_ctx(sd):
    st = sd.init()
    b = sd.scfg.base.batch_size
    kb = jax.ShapeDtypeStruct((STREAM_BATCHES, b), jnp.uint32)
    vb = jax.ShapeDtypeStruct((STREAM_BATCHES, b), jnp.bool_)
    stream = sd._make_stream(b // sd.n_shards)
    return {"lowered": stream.lower(st, kb, vb), "leaves": _leaf_spec(st)}


def serving_entry(*, variant: str = "rlbsbf", width: int = 256,
                  probe: bool = True,
                  name: Optional[str] = None) -> EntryPoint:
    """The serving executor's device path: the padded DONATED step at one
    batch bucket (``Dedup.process_padded(donate=True)``, DESIGN §5.2); the
    probe drives ragged request batches through ``MicroBatchExecutor``
    twice and checks the per-bucket compile cache is stable."""
    if name is None:
        name = f"serving/process-padded/{variant}/w{width}"
    cfg = _canon_cfg(variant, "planes")

    def build():
        from ..core.engine import Dedup
        d = Dedup(cfg)
        st = jax.eval_shape(functools.partial(init_state, cfg))
        k = jax.ShapeDtypeStruct((width,), jnp.uint32)
        v = jax.ShapeDtypeStruct((width,), jnp.bool_)
        return d._batched_donated.lower(st, k, v)

    def leaves():
        st = jax.eval_shape(functools.partial(init_state, cfg))
        return _leaf_spec(st)

    def retrace():
        from ..serve.frontend import MicroBatchExecutor
        ex = MicroBatchExecutor(
            cfg, lambda batch: np.zeros(len(batch["key"])),
            buckets=(64, width))
        rng = np.random.default_rng(1)

        def drive():
            for n in (10, 64, 100, width):
                ex.run({"key": rng.integers(0, 1 << 20, n,
                                            dtype=np.uint32)})
            return ex.engine.process_cache_size()
        first, second = drive(), drive()
        if second != first:
            return [f"replaying the same bucket widths grew the step "
                    f"cache {first} -> {second} (one trace per bucket "
                    f"expected)"]
        return []

    return EntryPoint(
        name=name, tags=frozenset({"step", "serving", "donated"}), cfg=cfg,
        build=build, leaves=leaves,
        retrace_probe=retrace if probe else None, extra=_thresholds(cfg))


def fleet_step_entry(*, variant: str = "rlbsbf", backend: str = "jnp",
                     n_tenants: int = 8, probe: bool = False,
                     name: Optional[str] = None) -> EntryPoint:
    """The tenant fleet's mixed-batch step (``FleetDedup.process``, DESIGN
    §4.6): route-by-tenant + ONE vmapped templated step over the stacked
    (T, ...) state. Not donated (interactive contract, like ``step/``);
    the probe checks the per-width compile cache stays at one entry."""
    if name is None:
        name = f"fleet-step/{variant}/{backend}/t{n_tenants}"
    cfg = _canon_cfg(variant, "planes", backend=backend,
                     n_tenants=n_tenants)

    def make_fleet():
        from ..core.fleet import FleetDedup
        return FleetDedup(cfg)

    def build():
        from ..core.fleet import init_fleet_state
        fleet = make_fleet()
        st = jax.eval_shape(functools.partial(
            init_fleet_state, cfg, event_capacity=fleet.capacity))
        b = cfg.batch_size
        k = jax.ShapeDtypeStruct((b,), jnp.uint32)
        t = jax.ShapeDtypeStruct((b,), jnp.int32)
        v = jax.ShapeDtypeStruct((b,), jnp.bool_)
        return jax.jit(fleet._fleet_fn()).lower(st, k, t, v)

    def retrace():
        fleet = make_fleet()
        st = fleet.init()
        rng = np.random.default_rng(2)
        keys = rng.integers(0, 1 << 20, cfg.batch_size, dtype=np.uint32)
        tens = rng.integers(0, n_tenants, cfg.batch_size).astype(np.int32)
        for _ in range(2):
            st, _ = fleet.process(st, jnp.asarray(keys), jnp.asarray(tens))
        if fleet.process_cache_size() != 1:
            return [f"replaying the same-width mixed batch grew the fleet "
                    f"step cache to {fleet.process_cache_size()} (one "
                    f"trace per width expected)"]
        return []

    return EntryPoint(
        name=name, tags=frozenset({"step", "fleet", backend}), cfg=cfg,
        build=build, retrace_probe=retrace if probe else None,
        extra=_thresholds(cfg))


def fleet_stream_entry(*, variant: str = "rlbsbf", backend: str = "jnp",
                       n_tenants: int = 8,
                       name: Optional[str] = None) -> EntryPoint:
    """The fleet's donated stream scan (``FleetDedup.run_stream``, §4.6) —
    the whole mixed-tenant stream in one dispatch, stacked state aliased in
    place like every other donated scan."""
    if name is None:
        name = f"fleet-stream/{variant}/{backend}/t{n_tenants}"
    cfg = _canon_cfg(variant, "planes", backend=backend,
                     n_tenants=n_tenants)
    ctx = _lazy(lambda: _fleet_stream_ctx(cfg))

    return EntryPoint(
        name=name,
        tags=frozenset({"stream", "fleet", "donated", backend}), cfg=cfg,
        build=lambda: ctx()["lowered"], leaves=lambda: ctx()["leaves"],
        extra=_thresholds(cfg))


def _fleet_stream_ctx(cfg: DedupConfig):
    from ..core.fleet import FleetDedup, init_fleet_state
    fleet = FleetDedup(cfg)
    st = jax.eval_shape(functools.partial(
        init_fleet_state, cfg, event_capacity=fleet.capacity))
    b = cfg.batch_size
    kb = jax.ShapeDtypeStruct((STREAM_BATCHES, b), jnp.uint32)
    tb = jax.ShapeDtypeStruct((STREAM_BATCHES, b), jnp.int32)
    vb = jax.ShapeDtypeStruct((STREAM_BATCHES, b), jnp.bool_)
    fleet_step = fleet._fleet_fn()

    def stream(state, kb, tb, vb):
        def body(state, xs):
            kk, tt, vv = xs
            state, res = fleet_step(state, kk, tt, vv)
            return state, (res.dup, res.overflow)

        state, (dups, ovfs) = jax.lax.scan(body, state, (kb, tb, vb))
        return state, dups, ovfs

    lowered = jax.jit(stream, donate_argnums=0).lower(st, kb, tb, vb)
    return {"lowered": lowered, "leaves": _leaf_spec(st)}


# ------------------------------------------------------------------ matrix


# dense8 is the reference layout of the non-windowed variants; swbf/cms/hh
# are planes-only by construction (config.validate)
DENSE8_VARIANTS = ("rsbf", "bsbf", "bsbfsd", "rlbsbf", "sbf")
# streams scan the step inside a donated carry — sweep one representative
# per distinct carry structure (1-bit planes, counter planes, window ring,
# pure-add sketch, dense8 reference) on both backends where they differ
STREAM_MATRIX = (
    ("rlbsbf", "planes", "jnp"), ("rlbsbf", "planes", "pallas"),
    ("rlbsbf", "dense8", "jnp"),
    ("sbf", "planes", "jnp"), ("sbf", "planes", "pallas"),
    ("swbf", "planes", "jnp"), ("swbf", "planes", "pallas"),
    ("cms", "planes", "jnp"),
)


def iter_entry_points() -> List[EntryPoint]:
    """The full sweep matrix: every registered SketchSpec × layout ×
    backend step, representative donated streams, the sharded
    serial/pipelined/rebalance scans, the serving executor, and the
    ``debug_exact_load`` escape hatch (whose O(s) reduce is the baseline
    policy's worked example). Building the list is free — nothing traces
    until a rule inspects an entry."""
    eps: List[EntryPoint] = []
    for variant in SKETCHES:
        eps.append(step_entry(_canon_cfg(variant, "planes")))
        eps.append(step_entry(_canon_cfg(variant, "planes",
                                         backend="pallas")))
    for variant in DENSE8_VARIANTS:
        eps.append(step_entry(_canon_cfg(variant, "dense8")))
    # the escape hatch DOES reduce over the filter — kept in the matrix,
    # suppressed in scripts/lint_baseline.json with its justification
    eps.append(step_entry(_canon_cfg("rlbsbf", "planes",
                                     debug_exact_load=True)))
    for i, (variant, layout, backend) in enumerate(STREAM_MATRIX):
        eps.append(stream_entry(_canon_cfg(variant, layout,
                                           backend=backend),
                                probe=(i == 0)))
    eps.append(sharded_stream_entry(pipeline=False))
    eps.append(sharded_stream_entry(pipeline=True, probe=True))
    eps.append(sharded_stream_entry(pipeline=True, rebalance_buckets=4))
    eps.append(serving_entry())
    # tenant fleets (§4.6): the routed vmapped step on both backends plus
    # one representative donated fleet stream per family
    eps.append(fleet_step_entry(probe=True))
    eps.append(fleet_step_entry(backend="pallas"))
    eps.append(fleet_step_entry(variant="swbf"))
    eps.append(fleet_stream_entry())
    eps.append(fleet_stream_entry(variant="sbf"))
    return eps


def get_entry(name: str) -> EntryPoint:
    for ep in iter_entry_points():
        if ep.name == name:
            return ep
    raise KeyError(f"no entry point named {name!r}")
