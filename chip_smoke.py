#!/usr/bin/env python
"""Chip smoke: run the dedup engine's main path once on a TPU and check it.

    python chip_smoke.py [--seed N]          # one chip: phases A-E
    python chip_smoke.py --four-chips        # the multi-chip path only

One process drives the chip; it starts no other. Phases, each printing its
compile seconds, run seconds and checks on lines of their own:

  A  device: a TPU or exit non-zero at once (no CPU fallback);
  B  the paper's stream at full filter size: RLBSBF at 512 MB (512 MiB of
     planes in HBM) and the SBF baseline at 64 MB over a 2^26-key stream,
     60% distinct: the first 2^18 verdicts equal the same engine on the
     host CPU, and FPR <= the config's target against the exact truth;
  C  cross-backend parity: every variant at the pinned-digest size, the
     TPU's verdicts equal to the CPU's (exactly), and the pinned digests;
  D  the fused Pallas kernels compiled for the chip (interpret=False), one
     bitset (rlbsbf) and one counter (sbf) variant at the largest filter
     the VMEM guard admits: planes, load and verdicts equal to the jnp step,
     both with the kernel built by hand and through ``Dedup`` with
     ``backend="pallas"``;
  E  serving: ``ServeFrontend`` answers 2,000 concurrent ``submit()``
     calls; its verdicts equal ``replay_schedule`` on its schedule.

``--four-chips`` runs ``ShardedDedup`` over a 4-chip mesh with a 1 GiB
filter (256 MiB per chip): elastic routing with the rebalance monitor on
equals the same config on a 1-chip mesh with zero overflow, and the static
pipelined scan equals the serial scan on the same 4 chips.

A failed check exits non-zero. The last line of standard output is the
JSON verdict ``{"ok": true, "device": {...}}``, printed only on success.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
FAILED = []

# sizes (a CPU rehearsal of this script shrinks them)
B_KEYS, B_PREFIX = 1 << 26, 1 << 18
B_CONFIGS = (("rlbsbf", 512), ("sbf", 64))    # (variant, paper memory MB)
C_KEYS = 1 << 17
D_BITS = 1 << 24        # the largest filter the 8 MiB VMEM guard admits
D_RUNS = (("rlbsbf", False, 256), ("sbf", False, 256), ("sbf", True, 32))
D_INTERPRET = False
E_CLIENTS, E_PER_CLIENT = 20, 100
F_TOTAL_BITS, F_KEYS, F_BUCKETS = 8 * 2**30, 1 << 18, 32   # 1 GiB filter

# JAX's own compile-time events: lowering to MLIR and the backend compile.
# Tracing is left in the run seconds: nested jits emit overlapping trace
# events that would count twice.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
COMPILE_S = [0.0]


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"  check {name}: {'PASS' if ok else 'FAIL'} {detail}".rstrip(),
          flush=True)
    if not ok:
        FAILED.append(name)


def phase(tag: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"phase {tag}: {body}", flush=True)


def _on_event(event: str, duration: float, **_) -> None:
    if event in COMPILE_EVENTS:
        COMPILE_S[0] += duration


def timed(fn):
    """(result, seconds) of ``fn()``, the result's arrays made ready."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def timed_split(fn):
    """(result, compile seconds, run seconds) of one ``fn()`` call: the
    compile seconds are JAX's lowering and backend-compile events during
    the call, the run seconds the rest of its wall time (tracing
    included)."""
    c0 = COMPILE_S[0]
    out, total = timed(fn)
    compile_s = COMPILE_S[0] - c0
    return out, compile_s, total - compile_s


# ----------------------------------------------------------------- phase A //
def phase_a():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform = "
              f"{dev.platform!r}); nothing to smoke", file=sys.stderr)
        sys.exit(2)
    import importlib.metadata as md
    phase("A device", jax=jax.__version__,
          libtpu=md.version("libtpu"), kind=repr(dev.device_kind),
          count=len(jax.devices()))
    return dev


# ----------------------------------------------------------------- phase B //
def phase_b(dev, seed: int):
    import jax
    import jax.numpy as jnp
    from repro.configs.paper_dedup import paper_config
    from repro.core import Dedup
    from repro.data.streams import controlled_distinct_stream

    n, prefix = B_KEYS, B_PREFIX
    t0 = time.perf_counter()
    keys_np, truth = controlled_distinct_stream(n, 0.60, seed)
    keys = jax.device_put(keys_np, dev)
    phase("B stream", keys=n, distinct_frac=0.60, seed=seed,
          gen_s=f"{time.perf_counter() - t0:.3f}")
    cpu = jax.devices("cpu")[0]
    for variant, mb in B_CONFIGS:
        cfg = paper_config(variant, mb, layout="planes")
        eng = Dedup(cfg)
        before = dev.memory_stats() or {}
        (st, dups), compile_s, run_s = timed_split(
            lambda: eng.run_stream(eng.init(), keys))
        dups = np.asarray(dups)
        state_bytes = int(st.bits.nbytes)
        del st
        after = dev.memory_stats() or {}
        # the allocator's peak is process-wide and never reset: it is this
        # config's own only where this run raised it
        p0, p1 = before.get("peak_bytes_in_use"), after.get("peak_bytes_in_use")
        own_peak = ("not reported" if p1 is None else
                    p1 if p0 is None or p1 > p0 else f"<={p1}")
        b = cfg.batch_size
        mem = eng._stream.lower(
            jax.eval_shape(eng.init),
            jax.ShapeDtypeStruct((-(-n // b), b), jnp.uint32),
            jax.ShapeDtypeStruct((-(-n // b), b), jnp.bool_),
        ).compile().memory_analysis()
        fp = int((dups & ~truth).sum())
        fn = int((~dups & truth).sum())
        fpr = fp / max(1, int((~truth).sum()))
        fnr = fn / max(1, int(truth.sum()))
        phase(f"B {variant}@{mb}MB", compile_s=f"{compile_s:.3f}",
              run_s=f"{run_s:.3f}", keys_per_s=f"{n / run_s:.1f}",
              filter_bytes=state_bytes, fpr=f"{fpr:.6g}", fnr=f"{fnr:.6g}",
              live_bytes_before=before.get("bytes_in_use", "not reported"),
              own_peak_bytes_in_use=own_peak,
              program_argument_bytes=mem.argument_size_in_bytes,
              program_output_bytes=mem.output_size_in_bytes,
              program_temp_bytes=mem.temp_size_in_bytes,
              program_alias_bytes=mem.alias_size_in_bytes)
        print(f"  memory_stats {variant}@{mb}MB: {json.dumps(after)}",
              flush=True)
        check(f"B.{variant}.filter_bytes",
              state_bytes == cfg.memory_bits // 8, f"{state_bytes} B")
        check(f"B.{variant}.fpr_within_target", fpr <= cfg.fpr_t,
              f"fpr={fpr:.6g} <= fpr_t={cfg.fpr_t}")
        t0 = time.perf_counter()
        with jax.default_device(cpu):
            ceng = Dedup(cfg)
            _, cd = ceng.run_stream(ceng.init(), jnp.asarray(keys_np[:prefix]))
            cd = np.asarray(cd)
        ndiff = int((cd != dups[:prefix]).sum())
        check(f"B.{variant}.cpu_parity_first_2^18", ndiff == 0,
              f"{ndiff} of {prefix} verdicts differ "
              f"(cpu run {time.perf_counter() - t0:.3f} s)")
        del eng, ceng


# ----------------------------------------------------------------- phase C //
C_VARIANTS = ("sbf", "rsbf", "bsbf", "bsbfsd", "rlbsbf", "swbf", "cms", "hh")


def phase_c(dev, seed: int):
    import jax
    import jax.numpy as jnp
    from repro.core import Dedup
    from repro.core.digests import PINNED_DIGESTS, pinned_config, run_digest
    from repro.data.streams import controlled_distinct_stream

    n = C_KEYS
    keys_np, _ = controlled_distinct_stream(n, 0.60, seed + 1)
    cpu = jax.devices("cpu")[0]
    for v in C_VARIANTS:
        cfg = pinned_config(v, layout="planes", packed=False)
        eng = Dedup(cfg)
        keys = jax.device_put(keys_np, dev)
        (_, dups), compile_s, run_s = timed_split(
            lambda: eng.run_stream(eng.init(), keys))
        dups = np.asarray(dups)
        with jax.default_device(cpu):
            ceng = Dedup(cfg)
            _, cd = ceng.run_stream(ceng.init(), jnp.asarray(keys_np))
            cd = np.asarray(cd)
        ndiff = int((cd != dups).sum())
        phase(f"C {v}", compile_s=f"{compile_s:.3f}", run_s=f"{run_s:.3f}",
              keys=n, differing_verdicts=ndiff)
        check(f"C.{v}.tpu_equals_cpu", ndiff == 0, f"{ndiff} of {n} differ")
    for name in sorted(PINNED_DIGESTS):
        got = run_digest(pinned_config(name))
        check(f"C.digest.{name}", got == PINNED_DIGESTS[name],
              f"tpu {got} pinned {PINNED_DIGESTS[name]}")


# ----------------------------------------------------------------- phase D //
def phase_d(dev, seed: int):
    import jax
    import jax.numpy as jnp
    from repro.core import Dedup, DedupConfig
    from repro.data.streams import controlled_distinct_stream
    from repro.kernels.common import (VMEM_FILTER_BYTES_LIMIT,
                                      fused_resident_bytes)
    from repro.kernels.fused_template import make_fused_step

    bits = D_BITS
    for variant, accumulate, n_batches in D_RUNS:
        cfg = DedupConfig.for_variant(variant, memory_bits=bits,
                                      layout="planes")
        pcfg = dataclasses.replace(cfg, backend="pallas",
                                   kernel_accumulate=accumulate)
        b = cfg.batch_size
        keys_np, _ = controlled_distinct_stream(n_batches * b, 0.60,
                                                seed + 2)
        kb = jax.device_put(keys_np.reshape(n_batches, b), dev)
        vb = jnp.ones(kb.shape, bool, device=dev)
        step = make_fused_step(pcfg, interpret=D_INTERPRET)

        def stream(state, kb, vb, step=step):
            def body(st, xs):
                st, res = step(st, *xs)
                return st, res.dup
            return jax.lax.scan(body, state, (kb, vb))

        eng = Dedup(cfg)
        t0 = time.perf_counter()
        compiled = jax.jit(stream, donate_argnums=0).lower(
            eng.init(), kb, vb).compile()
        compile_s = time.perf_counter() - t0
        mosaic = "tpu_custom_call" in compiled.as_text()
        (pst, pd), run_s = timed(lambda: compiled(eng.init(), kb, vb))
        (jst, jd), _ = timed(lambda: eng.run_stream(eng.init(),
                                                    kb.reshape(-1)))
        # the route a user takes: Dedup picks interpret from the backend
        peng = Dedup(pcfg)
        lowered = peng._stream.lower(
            jax.eval_shape(peng.init), kb, vb).as_text()
        (_, ed), ecompile_s, erun_s = timed_split(
            lambda: peng.run_stream(peng.init(), kb.reshape(-1)))
        tag = f"{variant}{'+accumulate' if accumulate else ''}"
        phase(f"D {tag}", memory_bits=bits,
              resident_bytes=fused_resident_bytes(pcfg),
              batches=n_batches, compile_s=f"{compile_s:.3f}",
              run_s=f"{run_s:.3f}",
              keys_per_s=f"{n_batches * b / run_s:.1f}",
              dedup_compile_s=f"{ecompile_s:.3f}",
              dedup_run_s=f"{erun_s:.3f}")
        check(f"D.{tag}.within_vmem_guard",
              fused_resident_bytes(pcfg) <= VMEM_FILTER_BYTES_LIMIT)
        check(f"D.{tag}.compiled_mosaic_kernel", mosaic,
              "interpret=False, tpu_custom_call in the compiled HLO")
        check(f"D.{tag}.verdicts_equal_jnp",
              bool(np.array_equal(np.asarray(pd).reshape(-1),
                                  np.asarray(jd))))
        check(f"D.{tag}.planes_equal_jnp",
              bool(jnp.array_equal(pst.bits, jst.bits)))
        check(f"D.{tag}.load_equal_jnp",
              bool(np.array_equal(np.asarray(pst.load),
                                  np.asarray(jst.load))),
              f"load={np.asarray(pst.load).tolist()}")
        check(f"D.{tag}.position_equal_jnp",
              int(pst.position) == int(jst.position))
        check(f"D.{tag}.dedup_pallas_lowers_mosaic",
              "tpu_custom_call" in lowered,
              "Dedup(backend='pallas') stream, tpu_custom_call lowered")
        check(f"D.{tag}.dedup_pallas_verdicts_equal_jnp",
              bool(np.array_equal(np.asarray(ed), np.asarray(jd))))


# ----------------------------------------------------------------- phase E //
def phase_e(dev, seed: int):
    from repro.core import DedupConfig
    from repro.serve.frontend import ServeFrontend, replay_schedule

    cfg = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 24,
                                  layout="planes", batch_size=1024)
    rng = np.random.default_rng(seed + 3)
    n_clients, per_client = E_CLIENTS, E_PER_CLIENT
    keys = rng.integers(0, 700, size=(n_clients, per_client)).astype(
        np.uint32)

    def score_fn(batch):
        return np.asarray(batch["key"], np.float32) * 0.5

    async def serve():
        async with ServeFrontend(cfg, score_fn, record_schedule=True) as fe:
            async def client(ks):
                return await asyncio.gather(*(fe.submit(int(k)) for k in ks))
            results = await asyncio.gather(*(client(k) for k in keys))
        return fe, [r for rs in results for r in rs]

    t0 = time.perf_counter()
    fe, results = asyncio.run(serve())
    serve_s = time.perf_counter() - t0
    ex = fe.executor
    t0 = time.perf_counter()
    replay = replay_schedule(cfg, ex.schedule)
    replay_s = time.perf_counter() - t0
    n = n_clients * per_client
    flat = keys.reshape(-1)
    phase("E serve", requests=n, clients=n_clients, batches=ex.n_batches,
          mean_fill=f"{ex.mean_fill:.1f}",
          compile_s="in_run_s", run_s=f"{serve_s:.3f}",
          replay_s=f"{replay_s:.3f}")
    check("E.all_answered", len(results) == n
          and all(r.verdict == "ok" for r in results),
          f"{sum(r.verdict == 'ok' for r in results)} ok of {n}")
    check("E.values", all(float(r.value) == float(k) * 0.5
                          for r, k in zip(results, flat)))
    check("E.verdicts_equal_replay", replay == ex.digest(),
          f"digest {ex.digest()[:16]}")
    check("E.client_dups_equal_executor",
          sum(r.dup for r in results) == ex.n_dup, f"{ex.n_dup} dups")


# ----------------------------------------------------------- --four-chips //
def phase_four(seed: int):
    import jax
    import jax.numpy as jnp
    from repro.compat import make_mesh, set_mesh
    from repro.core import DedupConfig
    from repro.data.streams import zipf_range_stream
    from repro.dedup import ShardedDedup, ShardedDedupConfig

    devs = jax.devices()
    if len(devs) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(devs)}", file=sys.stderr)
        sys.exit(2)
    mesh4 = make_mesh((4, 1), ("data", "model"), devices=devs[:4])
    mesh1 = make_mesh((1, 1), ("data", "model"), devices=devs[:1])
    total_bits, n, nb = F_TOTAL_BITS, F_KEYS, F_BUCKETS
    keys_np, _ = zipf_range_stream(n, universe=1 << 22, a=1.2, seed=seed)
    keys = jnp.asarray(keys_np)

    def per_chip_bytes(x):
        return [int(sum(s.data.nbytes for s in x.addressable_shards
                        if s.device == d)) for d in devs[:4]]

    def run(mesh, base, **scfg):
        sd = ShardedDedup(ShardedDedupConfig(base=base, **scfg), mesh)
        with set_mesh(mesh):
            st, init_s = timed(sd.init)
            at_init = per_chip_bytes(st.bits)
            (st, dup, ovf), c, t = timed_split(
                lambda: sd.run_stream(st, keys))
        return (st, np.asarray(dup), int(np.asarray(ovf).sum()), init_s,
                f"compile_s={c:.3f}/run_s={t:.3f}", at_init)

    # elastic routing, rebalance monitor on, lossless dispatch
    base = DedupConfig.for_variant("rlbsbf", memory_bits=total_bits,
                                   layout="planes", rebalance_buckets=nb,
                                   rebalance_threshold=1.25)
    st4, d4, o4, i4, t4, per_chip = run(mesh4, base,
                                        capacity_factor=float(nb))
    n_reb = int(np.asarray(st4.router.n_rebalances))
    del st4
    st1, d1, o1, i1, t1, _ = run(mesh1, base, capacity_factor=float(nb))
    del st1
    phase("F elastic", keys=n, buckets=nb, filter_bytes_per_chip=per_chip,
          init_s_4=f"{i4:.3f}", chips_4=t4, chips_1=t1,
          rebalances_4=n_reb)
    check("F.elastic.state_sharded_at_init",
          all(b == total_bits // 8 // 4 for b in per_chip),
          f"bits bytes per chip {per_chip}")
    check("F.elastic.rebalance_fired", n_reb >= 1, f"{n_reb}")
    check("F.elastic.zero_overflow", o4 == 0 and o1 == 0, f"{o4}, {o1}")
    check("F.elastic.4chip_equals_1chip", bool(np.array_equal(d4, d1)),
          f"{int((d4 != d1).sum())} of {n} differ")

    # static hash routing: pipelined scan vs serial scan on the same chips
    base = DedupConfig.for_variant("rlbsbf", memory_bits=total_bits,
                                   layout="planes")
    stp, dp, op, _, tp, _ = run(mesh4, base, pipeline=True)
    sts, ds, os_, _, ts, _ = run(mesh4, base, pipeline=False)
    same_bits = bool(jax.jit(jnp.array_equal)(stp.bits, sts.bits))
    phase("F static", keys=n, pipelined=tp, serial=ts,
          overflow=[op, os_])
    check("F.static.pipelined_equals_serial_verdicts",
          bool(np.array_equal(dp, ds)), f"{int((dp != ds).sum())} differ")
    check("F.static.pipelined_equals_serial_state", same_bits
          and np.array_equal(np.asarray(stp.load), np.asarray(sts.load)))
    check("F.static.overflow_equal", op == os_, f"{op} vs {os_}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run the 4-chip sharded path and its references "
                         "only")
    args = ap.parse_args(argv)

    dev = phase_a()
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.compat import configure_compile_cache
    print(f"compile cache: {configure_compile_cache()}", flush=True)
    import jax

    if args.four_chips:
        phase_four(args.seed)
    else:
        for fn in (phase_b, phase_c, phase_d, phase_e):
            fn(dev, args.seed)
    if FAILED:
        print(f"chip_smoke: {len(FAILED)} check(s) failed: "
              f"{', '.join(FAILED)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
