"""Paper Fig 11: load (fraction of set bits) vs stream position — all
variants converge to a stable load; more memory converges later in records
but to lower FPR (the stability property SBF pioneered and the paper's
variants keep)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import Dedup
from repro.configs.paper_dedup import scaled_config

from .common import csv_row, save_artifact, stream

N_RECORDS = 1_000_000_000 // 256
VARIANTS = ("sbf", "rsbf", "bsbf", "bsbfsd", "rlbsbf")


def main(fast: bool = False) -> list:
    import jax
    n = N_RECORDS // (4 if fast else 1)
    keys, _ = stream(n, 0.15)
    rows, out = [], {}
    for mem_mb in (256, 512):
        for variant in VARIANTS:
            jax.clear_caches()                  # bound the LLVM JIT arena
            cfg = scaled_config(variant, mem_mb, batch_size=8192)
            d = Dedup(cfg)
            st = d.init()
            jkeys = jnp.asarray(keys)
            loads = []
            chunk = max(cfg.batch_size, n // 32 // cfg.batch_size * cfg.batch_size)
            for i in range(0, n - chunk + 1, chunk):
                st, _dup = d.run_stream(st, jkeys[i:i + chunk])
                loads.append(float(np.asarray(st.load).sum() /
                                   (cfg.n_rows * cfg.s)))
            # convergence: first window where the remaining range < 0.5%
            conv = next((i for i in range(len(loads))
                         if max(loads[i:]) - min(loads[i:]) < 5e-3),
                        len(loads))
            tag = f"fig_load/mem{mem_mb}MB/{variant}"
            out[tag] = {"loads": loads, "converged_at_chunk": conv,
                        "records_per_chunk": chunk}
            rows.append(csv_row(
                tag, 0.0,
                f"final_load={loads[-1]:.4f};converged_at={conv * chunk}"))
    save_artifact("fig_stability", out)
    return rows


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    print("\n".join(main()))
