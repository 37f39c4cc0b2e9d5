"""Read the control of a cell on the chip: one run of the cell at its own
size and load, then the control (the plain reference with one stated
guarantee broken) replayed over the same keys, compared with the
reference by the numbers that decide ``correct``. Prints the run's result
line and the control's numbers; the control has to fail at least one.

    python3 chipbench/tools/control.py <cell> <seconds> <seed> [<seed> ...]
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from chipbench import harness  # noqa: E402


def main(argv) -> int:
    name, seconds, seeds = argv[0], float(argv[1]), [int(s) for s in argv[2:]]
    cell = harness.resolve(name)
    devices = harness.chip_devices(int(cell["workload"]["chips"]))
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    failed_all = True
    for seed in seeds:
        result, info = harness.run_cell(
            name, seed, seconds, False, t_start=time.perf_counter(),
            devices=devices, cell=harness.resolve(name), control=True)
        ctl = info["control_checks"]
        fails = any(ctl[k] > harness.LIMITS[k] for k in ctl)
        failed_all &= fails
        print(f"seed={seed} correct={result['correct']} "
              f"program={json.dumps({k: v['value'] for k, v in result['checks'].items()})} "
              f"control={json.dumps(ctl)} control_fails={fails} "
              f"reference_s={info['reference_s']:.3f}", flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
