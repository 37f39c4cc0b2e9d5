"""Find the serving knee on the chip: run a serving cell's window at a
ladder of Poisson rates, ``runs`` times each, in one process, and print
per run the latency, the requests shed and whether the queue grew over
the window (the last fifth's median latency against the first fifth's).

A rate fails where any of its runs sheds or fails a request, grows by more
than ``GROWTH``, or has a median latency over ``P50_RISE`` times the
lowest rate's. The knee is the highest rate below the first that fails;
the traffic file then fixes its rate at 4/5 of it.

    python3 chipbench/tools/serve_sweep.py <cell> <seconds> <runs> <rate> ...
"""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from chipbench import harness  # noqa: E402
from chipbench.compiles import CompileCounter  # noqa: E402
from chipbench.loops import poisson  # noqa: E402

GROWTH = 1.5
P50_RISE = 2.0


def main(argv):
    name, seconds, runs = argv[0], float(argv[1]), int(argv[2])
    rates = sorted(float(r) for r in argv[3:])
    cell = harness.resolve(name)
    devices = harness.chip_devices(int(cell["workload"]["chips"]))
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    counter = CompileCounter()
    base_p50, knee = None, None
    for i, rate in enumerate(rates):
        traffic = dict(cell["traffic"],
                       arrival=dict(cell["traffic"]["arrival"],
                                    rate_per_s=rate))
        ok = True
        for j in range(runs):
            out = poisson.run({"config": cell["config"], "traffic": traffic,
                               "seed": 900 + 100 * j + i, "seconds": seconds,
                               "compiles": counter},
                              devices, harness.Tracer(False))
            served = out["latency_ms"][out["served"]]
            fifth = max(1, served.size // 5)
            growth = (float(np.median(served[-fifth:]))
                      / max(1e-9, float(np.median(served[:fifth]))))
            p50 = out["e2e"].get("verdict_p50_ms", float("nan"))
            base_p50 = base_p50 or p50
            ok &= (out["failed"] == 0 and growth <= GROWTH
                   and p50 <= P50_RISE * base_p50)
            print(f"rate={rate:.0f} run={j} requests={out['attempted']} "
                  f"shed_or_failed={out['failed']} p50_ms={p50:.3f} "
                  f"p95_ms={out['e2e'].get('verdict_p95_ms', float('nan')):.3f} "
                  f"growth_last_vs_first_fifth={growth:.2f} "
                  f"fill={out['layer']['fill'] / max(1, out['layer']['batches']):.1f} "
                  f"correct={all(v == 0 for v in out['checks'].values())} "
                  f"compiles_in_window={out['compiles_in_window']}",
                  flush=True)
        if not ok:
            break
        knee = rate
    print(f"knee {knee}: rate to fix {0.8 * knee if knee else None}")


if __name__ == "__main__":
    t0 = time.perf_counter()
    main(sys.argv[1:])
    print(f"sweep seconds {time.perf_counter() - t0:.1f}")
