import os
# the overlap-sweep worker subprocesses arrive with their own device count
# (and candidate flags) already locked into XLA_FLAGS — don't stack a second
# --xla_force_host_platform_device_count on top
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512"
                               ).strip()

"""§Perf hillclimbing driver: lower a cell under a config mutation, record
the loop-aware roofline terms, and append the (hypothesis, change, before,
after) record to experiments/perf_iterations.json.

    PYTHONPATH=src python -m repro.launch.hillclimb --exp <name>

Experiments are keyed to the three chosen cells (EXPERIMENTS.md §Perf):
  A. dedup-stream ingest (paper-representative)    — packed layout, capacity,
     incremental load
  B. deepseek-v2 decode_32k (worst memory-bound)   — MLA absorb, cache layout
  C. deepseek-v2 train_4k (MoE compute/collective) — dispatch strategy,
     bf16 accumulation, microbatching
plus a qwen3 decode cache-layout fix (SPMD involuntary-remat elimination)
and the cell-F collective-overlap flag sweep for the §4.5 pipelined ingest
(``--exp dedup-overlap``: greedy hillclimb over async-collective XLA flag
sets, each probed + timed in its own subprocess; accepted sets land next to
their throughput rows in the same artifact).
"""

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402
import numpy as np   # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.compat import make_mesh, set_mesh                       # noqa: E402
from repro.configs import get_arch                      # noqa: E402
from repro.configs.registry import LMArch               # noqa: E402
from repro.launch.analysis import analyze_compiled      # noqa: E402
from repro.launch.hw import HBM_BW, ICI_BW, PEAK_FLOPS_BF16  # noqa: E402
from repro.launch.mesh import make_production_mesh      # noqa: E402
from repro.optim import init_opt_state                  # noqa: E402

OUT = "experiments/perf_iterations.json"


def _ws(shape_tree, spec_tree, mesh):
    return jax.tree.map(
        lambda sd, s: jax.ShapeDtypeStruct(
            sd.shape, sd.dtype, sharding=NamedSharding(mesh, s)),
        shape_tree, spec_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def terms(rec):
    la = rec["loop_aware"]
    return {
        "flops": la["flops"],
        "hbm_bytes": la["hbm_bytes_essential"],
        "coll_bytes": la["collectives_bytes"].get("total", 0),
        "compute_s": la["flops"] / PEAK_FLOPS_BF16,
        "memory_s": la["hbm_bytes_essential"] / HBM_BW,
        "collective_s": la["collectives_bytes"].get("total", 0) / ICI_BW,
        "temp_bytes": rec["memory"].get("temp_size_in_bytes"),
        "copies_bytes": la["essential_by_op"].get("copy", 0),
    }


def lower_lm_cell(arch: LMArch, shape: str, mesh):
    cell = arch.shapes[shape]
    params_shape = arch.params_shape()
    pspecs = arch.param_specs(mesh)
    inputs = arch.input_specs(shape)
    bspecs = arch.batch_specs(shape, mesh)
    step = arch.step(shape)
    with set_mesh(mesh):
        if cell.kind == "train":
            opt_shape = jax.eval_shape(
                lambda: init_opt_state(arch.opt_config(), params_shape))
            ospecs = arch.opt_specs(mesh)
            fn = jax.jit(step, donate_argnums=(0, 1))
            args = (_ws(params_shape, pspecs, mesh),
                    _ws(opt_shape, ospecs, mesh),
                    *_ws(inputs, bspecs, mesh).values())
        elif cell.kind == "decode":
            fn = jax.jit(step, donate_argnums=(1,))
            i = _ws(inputs, bspecs, mesh)
            args = (_ws(params_shape, pspecs, mesh), i["cache"], i["token"],
                    i["pos"])
        else:
            fn = jax.jit(step)
            args = (_ws(params_shape, pspecs, mesh),
                    *_ws(inputs, bspecs, mesh).values())
        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        compiled = lowered.compile()
        dt = time.perf_counter() - t0
    rec = analyze_compiled(lowered, compiled)
    rec["compile_s"] = round(dt, 1)
    return rec


def lm_variant(arch_id: str, shape: str, label: str, hypothesis: str,
               mutate=None, accum=None):
    base_arch = get_arch(arch_id)
    cfg = base_arch.cfg if mutate is None else mutate(base_arch.cfg)
    accum_map = dict(base_arch.accum)
    if accum is not None:
        accum_map[shape] = accum
    arch = LMArch(arch_id, cfg, accum=accum_map)
    mesh = make_production_mesh()
    rec = lower_lm_cell(arch, shape, mesh)
    return {"cell": f"{arch_id}/{shape}/single", "label": label,
            "hypothesis": hypothesis, **terms(rec),
            "compile_s": rec["compile_s"],
            "collectives_counts": rec["loop_aware"]["collectives_counts"]}


def dedup_variant(label: str, hypothesis: str, packed: bool,
                  capacity_factor: float, memory_mb: int = 512,
                  batch: int = 1 << 20):
    from repro.core import DedupConfig
    from repro.dedup import ShardedDedup, ShardedDedupConfig

    mesh = make_production_mesh()
    cfg = DedupConfig.for_variant(
        "rlbsbf", memory_bits=memory_mb * 8 * 1024 * 1024, packed=packed)
    scfg = ShardedDedupConfig(base=cfg, mesh_axes=tuple(mesh.axis_names),
                              capacity_factor=capacity_factor)
    sd = ShardedDedup(scfg, mesh)
    step = sd.make_step(batch // sd.n_shards)
    state_shape = jax.eval_shape(sd.init)
    axes = tuple(mesh.axis_names)
    state_specs = jax.tree.map(
        lambda x: P(axes, *([None] * (x.ndim - 1))), state_shape)
    keys_sds = jax.ShapeDtypeStruct((batch,), np.uint32,
                                    sharding=NamedSharding(mesh, P(axes)))
    with set_mesh(mesh):
        t0 = time.perf_counter()
        lowered = step.lower(_ws(state_shape, state_specs, mesh), keys_sds)
        compiled = lowered.compile()
        dt = time.perf_counter() - t0
    rec = analyze_compiled(lowered, compiled)
    rec["compile_s"] = round(dt, 1)
    return {"cell": "dedup-stream/ingest_1048576/single", "label": label,
            "hypothesis": hypothesis, **terms(rec),
            "compile_s": rec["compile_s"],
            "collectives_counts": rec["loop_aware"]["collectives_counts"]}


EXPERIMENTS = {}


def exp(name):
    def deco(fn):
        EXPERIMENTS[name] = fn
        return fn
    return deco


# ---------------- cell A: the paper's technique ------------------------- //

@exp("dedup-baseline")
def dedup_baseline():
    return dedup_variant(
        "A0-baseline-dense8-cap2",
        "paper-faithful layout: one byte per bit, capacity factor 2.0",
        packed=False, capacity_factor=2.0)


@exp("dedup-packed")
def dedup_packed():
    return dedup_variant(
        "A1-packed-uint32",
        "32 bits/word packing cuts filter-state HBM traffic ~8-32x "
        "(probe gathers words; scatter builds packed deltas)",
        packed=True, capacity_factor=2.0)


@exp("dedup-capacity")
def dedup_capacity():
    return dedup_variant(
        "A2-packed-cap1.25",
        "routing buffers (S,C) dominate all-to-all bytes; capacity 2.0 -> "
        "1.25 cuts them 1.6x at <1e-4 overflow (Poisson tail at B/S=4096)",
        packed=True, capacity_factor=1.25)


# ---------------- cell F: pipelined-ingest collective overlap ---------- //
# The §4.5 double-buffered carry only pays off when the dispatch
# all_to_alls of batch t+1 genuinely overlap batch t's fused step — which
# on real hardware is the compiler's call, steered by async-collective
# flags. Candidate sets are seeded from the saxml serving flag sets
# (SNIPPETS.md: the CONV set's async collective-permute + windowed-einsum
# pair, the prefetch/loop-optimizer set), plus the CPU scheduler/codegen
# analogues that are live on the open-source host backend. XLA_FLAGS are
# locked at first jax init AND an unknown flag aborts the whole process,
# so every candidate is parse-probed and then timed in its own subprocess;
# unsupported sets are recorded (not crashed on) so the same sweep is
# rerunnable on a TPU build where they resolve.

OVERLAP_CELL = "dedup-stream/pipelined_ingest_8dev/overlap"
OVERLAP_DEVICES = 8
OVERLAP_ACCEPT = 1.02  # greedy accept threshold: >2% over the incumbent

OVERLAP_CANDIDATES = (
    ("F1-async-collective-permute",
     ("--xla_enable_async_collective_permute=true",),
     "saxml CONV set: async collective-permute lets the pipelined "
     "carry's key/count exchanges run while the fused step computes"),
    ("F2-windowed-einsum",
     ("--xla_jf_spmd_threshold_for_windowed_einsum_mib=0",
      "--xla_tpu_spmd_unroll_windowed_einsum=true"),
     "saxml CONV set: windowed einsum + unroll overlaps the per-window "
     "collective with compute inside the SPMD partitioner"),
    ("F3-prefetch-loop-optimizer",
     ("--xla_tpu_enforce_prefetch_fifo_order=true",
      "--xla_tpu_memory_bound_loop_optimizer_options=enabled:true"),
     "saxml memory-bound set: FIFO prefetch + loop optimizer keep the "
     "scan body's filter-plane loads ahead of the step"),
    ("F4-concurrency-scheduler",
     ("--xla_cpu_enable_concurrency_optimized_scheduler=true",),
     "CPU analogue of async collectives: the concurrency-optimized "
     "scheduler interleaves independent ops across simulated shards"),
    ("F5-parallel-codegen",
     ("--xla_cpu_parallel_codegen_split_count=32",),
     "split LLVM codegen 32 ways: faster compile AND more module-level "
     "parallelism for the 8-shard scan body"),
    ("F6-vector-width",
     ("--xla_cpu_prefer_vector_width=512",),
     "wider vectors for the popcount/probe inner loops of the fused step"),
)


def _overlap_env(flags):
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
    env = dict(os.environ)
    env["XLA_FLAGS"] = " ".join(
        (f"--xla_force_host_platform_device_count={OVERLAP_DEVICES}",)
        + tuple(flags))
    env["JAX_PLATFORMS"] = "cpu"     # simulated devices; never the chip
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env


def _overlap_probe(flags) -> bool:
    """An unknown flag aborts the interpreter at jax init — probe parse
    validity in a throwaway subprocess before paying for a timed run."""
    out = subprocess.run([sys.executable, "-c", "import jax; jax.devices()"],
                         env=_overlap_env(flags), capture_output=True)
    return out.returncode == 0


def _overlap_time(flags):
    """Elems/s of the timed worker under the candidate flag set, or None."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.hillclimb", "--overlap-worker"],
        env=_overlap_env(flags), capture_output=True, text=True)
    if out.returncode != 0:
        return None
    return float(json.loads(
        out.stdout.strip().splitlines()[-1])["elems_per_s"])


def overlap_worker(n: int = 1 << 17) -> None:
    """Runs inside the subprocess (XLA_FLAGS already locked): paper-scale
    pipelined swbf ingest at 8 simulated devices, best-of-3 wall-clock."""
    import jax.numpy as jnp

    from repro.compat import make_mesh, set_mesh
    from repro.core import DedupConfig
    from repro.dedup import ShardedDedup, ShardedDedupConfig

    assert len(jax.devices()) == OVERLAP_DEVICES, jax.devices()
    mesh = make_mesh((OVERLAP_DEVICES, 1), ("data", "model"))
    cfg = DedupConfig.for_variant("swbf", window=8, memory_bits=1 << 20,
                                  batch_size=16384, packed=True)
    sd = ShardedDedup(ShardedDedupConfig(base=cfg, pipeline=True), mesh)
    keys = jnp.asarray(np.random.default_rng(5).integers(
        0, 1 << 21, n).astype(np.uint32))
    with set_mesh(mesh):
        _, dup, _ = sd.run_stream(sd.init(), keys)  # compile
        np.asarray(dup)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            _, dup, _ = sd.run_stream(sd.init(), keys)
            np.asarray(dup)
            best = min(best, time.perf_counter() - t0)
    print(json.dumps({"elems_per_s": n / best}))


@exp("dedup-overlap")
def dedup_overlap():
    base = _overlap_time(())
    rows = [{"cell": OVERLAP_CELL, "label": "F0-overlap-baseline",
             "hypothesis": "pipelined §4.5 ingest under default flags — "
                           "the incumbent every candidate must beat",
             "flags": [], "elems_per_s": base, "speedup": 1.0,
             "accepted": base is not None}]
    accepted, best = [], base
    for label, flags, hypothesis in OVERLAP_CANDIDATES:
        row = {"cell": OVERLAP_CELL, "label": label,
               "hypothesis": hypothesis, "flags": list(flags)}
        if not _overlap_probe(accepted + list(flags)):
            row.update(status="unsupported-flag-on-backend", accepted=False)
        else:
            eps = _overlap_time(tuple(accepted) + flags)
            row["elems_per_s"] = eps
            row["speedup"] = (eps / base) if (eps and base) else None
            if eps is not None and best is not None \
                    and eps > best * OVERLAP_ACCEPT:
                accepted, best = accepted + list(flags), eps
                row["accepted"] = True
            else:
                row["accepted"] = False
        rows.append(row)
    rows.append({"cell": OVERLAP_CELL, "label": "F*-overlap-accepted",
                 "hypothesis": "greedy union of every accepted set — the "
                               "flag line a deployment should export",
                 "accepted_flags": accepted, "elems_per_s": best,
                 "speedup": (best / base) if (best and base) else None,
                 "accepted": True})
    return rows


# ---------------- cell B: deepseek decode (memory-bound) --------------- //

@exp("mla-noabsorb")
def mla_noabsorb():
    return lm_variant(
        "deepseek-v2-236b", "decode_32k", "B0-baseline-naive-mla",
        "straightforward MLA decode re-materializes per-head K/V from the "
        "latent over all 32k cached positions each step",
        mutate=lambda c: dataclasses.replace(c, mla_absorb=False))


@exp("mla-absorb")
def mla_absorb():
    return lm_variant(
        "deepseek-v2-236b", "decode_32k", "B1-absorbed-mla",
        "absorbing W_uk/W_uv into the query/output projections keeps "
        "attention in the 576-dim latent: kills the S*H*(nope+v) "
        "re-materialization flops AND its HBM traffic",
        mutate=lambda c: dataclasses.replace(c, mla_absorb=True))


@exp("mla-seqcache")
def mla_seqcache():
    from repro.distributed import sharding as shr
    orig = shr.transformer_cache_specs

    def seq_latent(cfg, mesh, cache_shape):
        b = shr.batch_axes(mesh)

        def leaf(path, leaf_sd):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            shape = leaf_sd.shape
            if name in ("ckv", "kpe"):
                return P(None, b, "model", None)
            if name == "kpos":
                return P(None, b, "model")
            return P(*(None for _ in shape))

        return jax.tree_util.tree_map_with_path(leaf, cache_shape)

    shr.transformer_cache_specs = seq_latent
    try:
        out = lm_variant(
            "deepseek-v2-236b", "decode_32k", "B2-absorbed+seq-cache",
            "after absorbing, the collective term is the latent-dim-sharded "
            "cache being re-gathered per step; sequence-sharding the latent "
            "cache keeps attention psum-only like the qwen3 D1 win",
            mutate=lambda c: dataclasses.replace(c, mla_absorb=True))
    finally:
        shr.transformer_cache_specs = orig
    return out


# ---------------- cell C: deepseek train (MoE) -------------------------- //

@exp("moe-einsum")
def moe_einsum():
    return lm_variant(
        "deepseek-v2-236b", "train_4k", "C0-baseline-gshard-einsum",
        "GShard dense dispatch (tokens,E,C) einsums — the faithful TPU-MoE "
        "baseline; predicted to exceed expert flops at E=160 top-6",
        mutate=lambda c: dataclasses.replace(c, moe_dispatch="einsum"))


@exp("moe-sort")
def moe_sort():
    return lm_variant(
        "deepseek-v2-236b", "train_4k", "C1-sort-dispatch",
        "argsort token-copies by expert + grouped matmul: dispatch cost "
        "O(T*k) data movement, independent of E -> compute term drops to "
        "the true expert flops",
        mutate=lambda c: dataclasses.replace(c, moe_dispatch="sort"))


@exp("train-bf16accum")
def train_bf16accum():
    # accum buffer dtype is plumbed via the arch step; emulate by raising
    # accum and switching dtype through a wrapper arch
    import repro.train.steps as steps
    orig = steps.make_train_step

    def patched(loss_fn, opt_cfg, accum_steps=1, accum_dtype=None):
        import jax.numpy as jnp
        return orig(loss_fn, opt_cfg, accum_steps, accum_dtype=jnp.bfloat16)

    steps.make_train_step = patched
    try:
        out = lm_variant(
            "deepseek-v2-236b", "train_4k", "C2-sort+bf16-accum",
            "fp32 grad-accum buffers are ~3.7GB/device x 2-3 live copies; "
            "bf16 accumulation halves them (optimizer moments stay fp32)")
    finally:
        steps.make_train_step = orig
    return out


@exp("train-accum16")
def train_accum16():
    return lm_variant(
        "deepseek-v2-236b", "train_4k", "C3-accum16",
        "halving the microbatch (accum 8->16) halves activation "
        "checkpoints + MoE transients; trades 2x more all-reduce rounds "
        "of the same total gradient bytes",
        accum=16)


@exp("mixtral-einsum")
def mixtral_einsum():
    return lm_variant(
        "mixtral-8x7b", "train_4k", "E0-mixtral-gshard-einsum",
        "inverse prediction of C0/C1: at E=8 top-2 the GShard dispatch "
        "einsums cost ~84 MFLOP/token vs 78 GFLOP/token of experts (0.1%) "
        "— einsum dispatch should be FINE here",
        mutate=lambda c: dataclasses.replace(c, moe_dispatch="einsum"))


@exp("mixtral-sort")
def mixtral_sort():
    return lm_variant(
        "mixtral-8x7b", "train_4k", "E1-mixtral-sort",
        "sort dispatch should be ~neutral at E=8 (the crossover between "
        "dispatch strategies is expert-count-driven, not a universal win)",
        mutate=lambda c: dataclasses.replace(c, moe_dispatch="sort"))


# ---------------- cell C': qwen3 train (most collective-bound) ---------- //

@exp("qwen3-train-baseline")
def qwen3_train_baseline():
    return lm_variant(
        "qwen3-8b", "train_4k", "C'0-baseline-hd-sharded-kv",
        "kv=8 heads don't divide model=16, so wk/wv shard head_dim; every "
        "flash kv-block then needs cross-shard reduction — thousands of "
        "all-gathers/all-reduces per step inside the layer x accum loops")


@exp("qwen3-train-kvrep")
def qwen3_train_kvrep():
    from repro.distributed import sharding as shr
    orig = shr.transformer_param_specs

    def kvrep_specs(cfg, mesh, params_shape, fsdp=False):
        specs = orig(cfg, mesh, params_shape, fsdp=fsdp)

        def fix(path, spec):
            name = path[-1].key if hasattr(path[-1], "key") else ""
            if name in ("wk", "wv"):
                return P(*(None for _ in spec))
            return spec

        return jax.tree_util.tree_map_with_path(
            fix, specs, is_leaf=lambda x: isinstance(x, P))

    shr.transformer_param_specs = kvrep_specs
    try:
        out = lm_variant(
            "qwen3-8b", "train_4k", "C'1-replicated-kv+expand",
            "Megatron GQA treatment: replicate the small wk/wv (16M params), "
            "expand K/V to the 32 query heads pre-attention (no (Kv,G) "
            "grouping reshape) — attention shards on H and goes "
            "collective-free; costs 16x duplicated KV-proj flops "
            "(~0.5% of layer flops)",
            mutate=lambda c: dataclasses.replace(c, gqa_expand_kv=True))
    finally:
        shr.transformer_param_specs = orig
    return out


# ---------------- bonus: qwen3 decode cache layout ---------------------- //

@exp("qwen3-decode-baseline")
def qwen3_decode_baseline():
    """Baseline = the pre-optimization head_dim-sharded cache (the rule that
    was default before §Perf D promoted sequence sharding)."""
    from repro.distributed import sharding as shr
    orig = shr.transformer_cache_specs

    def hd_sharded(cfg, mesh, cache_shape):
        b = shr.batch_axes(mesh)

        def leaf(path, leaf_sd):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            shape = leaf_sd.shape
            if name in ("k", "v"):
                return P(None, b, None, None, "model")
            if name in ("ckv", "kpe"):
                return P(None, b, None, "model")
            if name == "kpos":
                return P(None, b, None)
            return P(*(None for _ in shape))

        return jax.tree_util.tree_map_with_path(leaf, cache_shape)

    shr.transformer_cache_specs = hd_sharded
    try:
        out = lm_variant(
            "qwen3-8b", "decode_32k", "D0-baseline-hd-sharded-cache",
            "kv=8 < model=16 so the cache shards head_dim; SPMD reports "
            "involuntary full remat (full-cache copies) at the attention "
            "einsum")
    finally:
        shr.transformer_cache_specs = orig
    return out


@exp("qwen3-decode-seqshard")
def qwen3_decode_seqshard():
    from repro.distributed import sharding as shr
    orig = shr.transformer_cache_specs

    def seq_sharded(cfg, mesh, cache_shape):
        b = shr.batch_axes(mesh)

        def leaf(path, leaf_sd):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            shape = leaf_sd.shape
            if name in ("k", "v"):
                return P(None, b, "model", None, None)
            if name in ("ckv", "kpe"):
                return P(None, b, "model", None)
            if name == "kpos":
                return P(None, b, "model")
            return P(*(None for _ in shape))

        return jax.tree_util.tree_map_with_path(leaf, cache_shape)

    shr.transformer_cache_specs = seq_sharded
    try:
        out = lm_variant(
            "qwen3-8b", "decode_32k", "D1-seq-sharded-cache",
            "shard the cache on the sequence dim instead (2048 slots/dev): "
            "attention becomes a psum over sequence shards and the "
            "partitioner's full-cache remat copies disappear")
    finally:
        shr.transformer_cache_specs = orig
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", help=f"one of {sorted(EXPERIMENTS)} or 'all'")
    ap.add_argument("--overlap-worker", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.overlap_worker:
        overlap_worker()
        return
    if not args.exp:
        ap.error("--exp is required")
    names = sorted(EXPERIMENTS) if args.exp == "all" else [args.exp]
    results = []
    if os.path.exists(OUT):
        with open(OUT) as f:
            results = json.load(f)
    for name in names:
        recs = EXPERIMENTS[name]()
        for rec in recs if isinstance(recs, list) else [recs]:
            results[:] = [r for r in results if r["label"] != rec["label"]]
            results.append(rec)
            if "compute_s" in rec:
                print(f"[hillclimb] {rec['label']}: "
                      f"compute={rec['compute_s']:.4f}s "
                      f"memory={rec['memory_s']:.4f}s "
                      f"collective={rec['collective_s']:.4f}s "
                      f"temp={rec['temp_bytes'] / 1e9 if rec['temp_bytes'] else 0:.1f}GB")
            else:
                eps = rec.get("elems_per_s")
                print(f"[hillclimb] {rec['label']}: "
                      f"eps={eps:,.0f} " if eps else
                      f"[hillclimb] {rec['label']}: "
                      f"{rec.get('status', 'no measurement')} ",
                      end="")
                print(f"speedup={rec['speedup']:.3f}x "
                      if rec.get("speedup") else "",
                      end="")
                print(f"flags={rec.get('accepted_flags', rec.get('flags'))} "
                      f"accepted={rec.get('accepted')}")
            with open(OUT, "w") as f:
                json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
