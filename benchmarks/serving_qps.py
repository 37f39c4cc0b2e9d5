"""Serving QPS/latency harness: the dynamic-batching front-end vs the
per-request synchronous loop (DESIGN.md §5.2).

    PYTHONPATH=src python -m benchmarks.serving_qps [--fast]

Closed-loop heavy-traffic driver: ``N_CLIENTS`` concurrent clients each
submit their next request the moment the previous one resolves, over a
zipf/uniform request mix (hot repeated keys + a fresh-key tail — the
paper's §1 URL-probe / online-transaction shape). Two scorers:

  * ``trivial`` — an arithmetic response: isolates the serving machinery
    itself (queue, coalescing, engine dispatch, vectorized cache);
  * ``transformer`` — a small LM prefill scorer (the model pads its own
    ragged miss-batches to a bucket, one trace per width): the realistic
    regime where batching the forward pass is most of the win.

Measured per scorer, into ``BENCH_serving.json`` (frozen ``baseline`` /
refreshed ``current`` envelope like every other artifact):

  * ``frontend``   — sustained QPS, p50/p99 per-request verdict latency,
    shed rate, cache/dup hit rates, mean batch fill, and the engine's
    compiled-trace count (``process_cache`` — the bucket contract);
  * ``per_request``— the same request sequence through the synchronous
    ``ServeSession`` one request at a time (the pre-frontend serving
    story);
  * ``speedup``    — frontend QPS / per-request QPS. The acceptance bar
    (``scripts/bench_check.py --serving``) is >= 2x;
  * ``parity``     — the front-end records its admitted schedule (bucket
    width + request batch, in admission order) and ``replay_schedule``
    re-runs it through a fresh SYNCHRONOUS engine: digest equality proves
    the async machinery returns bit-identical dedup verdicts to the
    synchronous path on the same request order (DESIGN.md §5.2).
"""

from __future__ import annotations

import asyncio
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DedupConfig
from repro.core.engine import next_pow2
from repro.data.streams import zipf_stream
from repro.models.transformer import TransformerConfig, init, prefill
from repro.serve import ServeFrontend, ServeSession, replay_schedule

from .common import csv_row, save_artifact

BENCH_PATH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                          "BENCH_serving.json"))
BUCKETS = (64, 256, 1024)
N_CLIENTS = 64
MAX_LIVE = 4
FLUSH_S = 2e-3
GATE_SPEEDUP = 2.0          # frontend must sustain >= 2x the per-request QPS
SEQ_LEN = 16                # transformer scorer context


def request_mix(n: int, seed: int = 7, zipf_frac: float = 0.7) -> np.ndarray:
    """(n,) uint32 request keys: ``zipf_frac`` hot zipf traffic (repeats —
    the dedup/cache win exists) blended with fresh uniform keys (the
    distinct tail), shuffled into one arrival order."""
    rng = np.random.default_rng(seed)
    n_z = int(n * zipf_frac)
    zk, _ = zipf_stream(n_z, universe=max(64, n // 8), a=1.2, seed=seed)
    uk = rng.integers(0, 1 << 32, size=n - n_z, dtype=np.uint64
                      ).astype(np.uint32)
    keys = np.concatenate([zk, uk])
    return keys[rng.permutation(n)]


def trivial_scorer(batch: dict) -> np.ndarray:
    return np.asarray(batch["key"], np.float64) * 2.0


def make_transformer_scorer():
    """Small-LM prefill scorer: request key -> SEQ_LEN pseudo-tokens ->
    last-position logit summary. Ragged miss-batches are padded to the
    smallest power-of-two bucket inside the scorer, so the forward pass
    compiles once per width — the same no-retrace discipline as the
    engine (DESIGN.md §5.2)."""
    cfg = TransformerConfig(name="serve-bench", n_layers=2, d_model=64,
                            n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
                            dtype=jnp.float32, attn_q_block=32,
                            attn_k_block=32)
    params = init(cfg, jax.random.PRNGKey(0))

    @functools.partial(jax.jit, static_argnums=())
    def fwd(tokens):
        logits = prefill(cfg, params, tokens)
        return logits[:, -1, :8].mean(axis=-1)

    mults = (np.arange(1, SEQ_LEN + 1, dtype=np.uint64)
             * np.uint64(0x9E3779B97F4A7C15))

    def scorer(batch: dict) -> np.ndarray:
        keys = np.asarray(batch["key"], np.uint64)
        m = keys.shape[0]
        # floor the ladder at 32: tiny miss-batches share one trace instead
        # of compiling widths 1, 2, 4, ... on the serving path
        width = max(32, next_pow2(m))
        keys_p = np.pad(keys, (0, width - m))
        tokens = ((keys_p[:, None] * mults[None, :]) >> np.uint64(32)
                  ).astype(np.int32) % cfg.vocab
        return np.asarray(fwd(jnp.asarray(tokens)))[:m]

    return scorer


def _dedup_cfg() -> DedupConfig:
    return DedupConfig.for_variant("rlbsbf", memory_bits=1 << 20,
                                   batch_size=BUCKETS[0])


def _percentiles(lat_s: list) -> dict:
    a = np.asarray(lat_s, np.float64) * 1e3
    return {"p50_ms": float(np.percentile(a, 50)),
            "p99_ms": float(np.percentile(a, 99))}


async def _drive(frontend: ServeFrontend, keys: np.ndarray,
                 n_clients: int) -> dict:
    """Closed loop: client c owns the c-th stride of the arrival order;
    each submits its next request as soon as the previous resolves."""
    lat: list = []

    async def client(c: int) -> None:
        for k in keys[c::n_clients]:
            t0 = time.perf_counter()
            res = await frontend.submit(int(k))
            if res.verdict == "ok":
                lat.append(time.perf_counter() - t0)

    t0 = time.perf_counter()
    await asyncio.gather(*(client(c) for c in range(n_clients)))
    dt = time.perf_counter() - t0
    return {"elapsed_s": dt, "lat": lat}


def measure_frontend(cfg: DedupConfig, score_fn, keys: np.ndarray,
                     warmup: np.ndarray) -> dict:
    """One front-end session: untimed warmup phase (jit compiles), then the
    timed closed-loop run. Returns the rates + the recorded schedule."""

    async def go():
        fe = ServeFrontend(cfg, score_fn, buckets=BUCKETS,
                           max_live_batches=MAX_LIVE, flush_timeout=FLUSH_S,
                           record_schedule=True)
        async with fe:
            await _drive(fe, warmup, N_CLIENTS)
            done0, shed0, sub0 = fe.n_completed, fe.n_shed, fe.n_submitted
            run = await _drive(fe, keys, N_CLIENTS)
            stats = fe.stats()
            stats["timed_completed"] = fe.n_completed - done0
            stats["timed_shed"] = fe.n_shed - shed0
            stats["timed_submitted"] = fe.n_submitted - sub0
            return fe, run, stats

    fe, run, stats = asyncio.run(go())
    out = {
        "qps": stats["timed_completed"] / run["elapsed_s"],
        **_percentiles(run["lat"]),
        "shed_rate": stats["timed_shed"] / max(1, stats["timed_submitted"]),
        "cache_hit_rate": stats["cache_hit_rate"],
        "dup_rate": stats["dup_rate"],
        "mean_fill": stats["mean_fill"],
        "batches": stats["batches"],
        "process_cache": stats["process_cache"],
        "n": int(keys.shape[0]),
        "clients": N_CLIENTS,
    }
    return out, fe.executor.schedule, fe.executor.digest()


def measure_per_request(cfg: DedupConfig, score_fn, keys: np.ndarray,
                        warmup: np.ndarray) -> dict:
    """The pre-frontend serving story: one synchronous ``ServeSession.serve``
    call per request — one engine dispatch (and one model call for every
    cache miss) per request."""
    sess = ServeSession(cfg, score_fn, buckets=BUCKETS)
    for k in warmup[:4 * BUCKETS[0]]:
        sess.serve({"key": np.asarray([k], np.uint32)})
    lat = []
    t0 = time.perf_counter()
    for k in keys:
        t1 = time.perf_counter()
        sess.serve({"key": np.asarray([k], np.uint32)})
        lat.append(time.perf_counter() - t1)
    dt = time.perf_counter() - t0
    return {"qps": keys.shape[0] / dt, **_percentiles(lat),
            "n": int(keys.shape[0])}


def measure_serving(fast: bool = True) -> dict:
    out = {}
    scorers = {
        "trivial": (trivial_scorer, 40_000 // (5 if fast else 1)),
        "transformer": (make_transformer_scorer(), 4_000 // (4 if fast else 1)),
    }
    for name, (score_fn, n) in scorers.items():
        keys = request_mix(n, seed=7)
        warmup = request_mix(max(512, n // 16), seed=11)
        fe_stats, schedule, digest = measure_frontend(
            _dedup_cfg(), score_fn, keys, warmup)
        base = measure_per_request(_dedup_cfg(), score_fn, keys, warmup)
        replay = replay_schedule(_dedup_cfg(), schedule)
        out[name] = {
            "frontend": fe_stats,
            "per_request": base,
            "speedup": fe_stats["qps"] / base["qps"],
            "digest": digest,
            "parity": bool(digest == replay),
        }
    return out


def write_serving_artifact(current: dict, meta: dict) -> str:
    prev = {}
    if os.path.exists(BENCH_PATH):
        with open(BENCH_PATH) as f:
            prev = json.load(f)
    baseline = prev.get("baseline")
    if baseline is None:
        baseline = dict(current, baseline_seeded_from_current=True)
    doc = {"schema": 1, "baseline": baseline, "current": current,
           "meta": meta}
    with open(BENCH_PATH, "w") as f:
        json.dump(doc, f, indent=1)
    return BENCH_PATH


def main(fast: bool = False) -> list:
    out = measure_serving(fast=fast)
    rows = []
    for name, rec in out.items():
        fe, pr = rec["frontend"], rec["per_request"]
        rows.append(csv_row(
            f"serving/{name}/frontend", 1e6 / fe["qps"],
            f"qps={fe['qps']:.0f} p50={fe['p50_ms']:.2f}ms "
            f"p99={fe['p99_ms']:.2f}ms shed={fe['shed_rate']:.3f} "
            f"fill={fe['mean_fill']:.0f}"))
        rows.append(csv_row(
            f"serving/{name}/per_request", 1e6 / pr["qps"],
            f"qps={pr['qps']:.0f} p50={pr['p50_ms']:.2f}ms"))
        rows.append(csv_row(
            f"serving/{name}/speedup", 0.0,
            f"x={rec['speedup']:.2f} parity={rec['parity']}"))
    save_artifact("serving_qps", out)
    path = write_serving_artifact(
        out, meta={"fast": fast, "backend": jax.default_backend(),
                   "buckets": list(BUCKETS), "clients": N_CLIENTS,
                   "max_live_batches": MAX_LIVE, "flush_s": FLUSH_S,
                   "captured": time.strftime("%Y-%m-%d")})
    rows.append(csv_row("serving/artifact", 0.0, path))
    return rows


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    fast = "--fast" in __import__("sys").argv
    print("\n".join(main(fast=fast)))
