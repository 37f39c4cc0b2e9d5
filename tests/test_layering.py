"""Layering: the dedup engine, the sharded stream and the serving front-end
import nothing of the model-zoo scaffold.

Each case imports one package in a fresh interpreter (``sys.modules`` of
this process already holds whatever other tests imported) and lists the
``repro`` modules that import loaded.
"""

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
FORBIDDEN = ("repro.models", "repro.train", "repro.optim", "repro.launch")


@pytest.mark.parametrize("package", ["repro.core", "repro.dedup",
                                     "repro.serve"])
def test_package_loads_no_zoo_module(package):
    code = (f"import sys, {package}\n"
            "print('\\n'.join(sorted(sys.modules)))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in (SRC, os.environ.get("PYTHONPATH")) if p))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    loaded = proc.stdout.split()
    assert package in loaded
    bad = [m for m in loaded
           if any(m == f or m.startswith(f + ".") for f in FORBIDDEN)]
    assert not bad, f"import {package} loaded {bad}"
