#!/usr/bin/env python
"""Fail fast when the installed JAX cannot run this repo.

    PYTHONPATH=src python scripts/check_env.py [--json PATH]

Exit 0 with a one-line-per-surface report when everything the repo needs is
available (directly or through the ``repro.compat`` adaptation layer);
exit 1 with an explicit list of the missing surfaces and what depends on
them otherwise — so a broken environment is a clear message at the start of
a session, not an ``AttributeError`` deep inside a shard_map trace.

``--json PATH`` additionally writes the machine-readable report (surface
map, missing list, verdict) — CI uploads it as an artifact next to the
lint report so a red run carries its environment with it.

The repo's pinned-JAX policy (DESIGN.md §4): version-sensitive jax APIs are
only touched through ``repro.compat``; this script is the runtime audit of
that contract.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

# what breaks when a surface is missing — the actionable half of the message
_DEPENDENTS = {
    "shard_map": "repro.dedup.sharded, repro.distributed.collectives, "
                 "tests/test_distributed.py",
    "make_mesh": "every mesh construction site (launch/mesh.py, tests, "
                 "examples)",
    "all_to_all": "the sharded dedup dispatch (repro.dedup.sharded)",
    "ppermute": "the elastic shard-rebalance permute (repro.dedup.sharded, "
                "repro.distributed.sharding.rebalance_collect; DESIGN §4.4)",
    "pallas": "the fused single-launch steps (repro.kernels.fused_template "
              "and its fused_step/fused_counter_step shims, "
              "cfg.backend='pallas')",
}


def _write_json(path: str, payload: dict) -> None:
    import json
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="check_env.py")
    ap.add_argument("--json", metavar="PATH", dest="json_path",
                    help="also write the report as JSON")
    args = ap.parse_args(argv)
    try:
        import jax  # noqa: F401
    except ImportError as e:
        print(f"check_env: FAIL — jax is not importable: {e}")
        if args.json_path:
            _write_json(args.json_path,
                        {"ok": False, "error": f"jax not importable: {e}"})
        return 1
    from repro import compat

    report = compat.jax_api_report()
    print(f"check_env: jax {report['jax_version']}")
    if report["jax_version"] != report["supported_jax"]:
        print(f"  WARNING: this repo runs on jax {report['supported_jax']}")
    print(f"  shard_map        : {'ok' if report['shard_map'] else 'MISSING'}")
    print(f"  set_mesh         : {'ok' if report['set_mesh'] else 'MISSING'}")
    print(f"  make_mesh        : {'ok' if report['make_mesh'] else 'MISSING'}")
    print(f"  all_to_all       : {'ok' if report['all_to_all'] else 'MISSING'}")
    print(f"  ppermute         : {'ok' if report['ppermute'] else 'MISSING'}")
    print(f"  pallas           : {'ok' if report['pallas'] else 'MISSING'}")

    # cost_analysis normalization must hold on a real compiled executable
    cost_ok, cost_err = True, None
    try:
        import jax.numpy as jnp
        c = jax.jit(lambda x: (x * x).sum()).lower(
            jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
        ca = compat.cost_analysis_dict(c)
        assert isinstance(ca, dict)
        print("  cost_analysis    : ok (normalized to dict)")
    except Exception as e:  # noqa: BLE001
        cost_ok, cost_err = False, f"{type(e).__name__}: {e}"
        print(f"  cost_analysis    : FAIL ({cost_err})")
        print("check_env: FAIL — compiled.cost_analysis() could not be "
              "normalized; launch/analysis.py and the roofline will break")

    missing = compat.missing_apis()
    ok = cost_ok and not missing
    if args.json_path:
        _write_json(args.json_path, {
            "ok": ok,
            "report": report,
            "cost_analysis_ok": cost_ok,
            "cost_analysis_error": cost_err,
            "missing": {name: _DEPENDENTS.get(name, "(core)")
                        for name in missing},
        })
    if not cost_ok:
        return 1
    if missing:
        print("check_env: FAIL — the installed jax lacks required APIs:")
        for name in missing:
            print(f"  - {name}: needed by {_DEPENDENTS.get(name, '(core)')}")
        print(f"  Install jax/jaxlib {compat.SUPPORTED_JAX}, the one JAX "
              f"this repo runs on.")
        return 1
    print("check_env: OK — repro.compat can satisfy every required surface")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
