"""JAX compatibility layer — the single choke point.

The repo runs on one JAX: jax/jaxlib 0.9.0 (libtpu 0.0.34 on the chip).
Policy (DESIGN.md §4): every version-sensitive jax surface is wrapped HERE,
call sites import from ``repro.compat`` and never spell the raw API, so a
jax upgrade only ever edits this one module. Resolution happens at *call*
time, not import time, so tests can monkeypatch a surface and
``scripts/check_env.py`` can report exactly what the installed jax provides.

Wrapped surfaces:

  * ``make_mesh``          — ``jax.make_mesh`` with every axis ``Auto``.
                             jax 0.9.0 defaults to ``Explicit`` axes, under
                             which the sharded scan's reshapes raise
                             ``ShardingTypeError``; this repo's shard_map
                             bodies assume the Auto (GSPMD) semantics.
  * ``configure_compile_cache`` — JAX's persistent compilation cache for
                             the chip entry points (never called by tests).
  * ``shard_map``          — ``jax.shard_map`` with the replication-check
                             flag ``check_vma``.
  * ``set_mesh``           — ``jax.set_mesh`` as a context manager.
  * ``cost_analysis_dict`` — ``compiled.cost_analysis()`` as a plain dict
                             (``None`` from a backend becomes ``{}``).
  * ``ppermute``           — the static collective-permute primitive behind
                             the elastic shard rebalance (DESIGN §4.4).
  * ``jit_cache_size``     — the private compiled-specialization count.
"""

from __future__ import annotations

import os
import pathlib
from typing import Any, Callable, Dict, Mapping, Optional, Sequence

import jax
from jax.sharding import AxisType

SUPPORTED_JAX = "0.9.0"
REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


# ------------------------------------------------------------ compile cache
def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at ``<repo>/.jax_cache``
    — a fixed path, since the directory is part of what a later run must
    find again. Called by ``chip_smoke.py`` and the benchmark entry points;
    tests never call it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------- make_mesh
def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types on every axis — the mesh
    every sharded path of this repo is written against. ``devices`` (e.g.
    a described topology's devices) is passed through."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names), **kw)


# ---------------------------------------------------------------- shard_map
def _resolve_shard_map() -> Callable:
    """The installed jax's shard_map callable."""
    return jax.shard_map


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check_vma: Optional[bool] = None, **kwargs) -> Callable:
    """``jax.shard_map``; ``check_vma=None`` leaves the library default."""
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    fn = _resolve_shard_map()
    return fn(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs)


# ----------------------------------------------------------------- set_mesh
def set_mesh(mesh):
    """Context manager making ``mesh`` the ambient mesh (``jax.set_mesh``)."""
    return jax.set_mesh(mesh)


# ----------------------------------------------------------------- ppermute
def _resolve_ppermute() -> Callable:
    """The installed jax's collective-permute callable."""
    return jax.lax.ppermute


def ppermute(x: Any, axis_name, perm) -> Any:
    """``jax.lax.ppermute``: send each device's value of ``x`` (a pytree)
    along the STATIC ``perm`` schedule of (source, dest) pairs over
    ``axis_name`` (a name or tuple of names, linearized like
    ``all_to_all``). The rebalance permute (DESIGN §4.4) builds its dynamic
    re-partition out of a full ring of these static sends — the permutation
    XLA compiles never depends on runtime load."""
    return _resolve_ppermute()(x, axis_name, perm)


# ------------------------------------------------------------ jit internals
def jit_cache_size(fn) -> int:
    """Compiled-specialization count of a jitted callable.

    jax only exposes this through the private ``_cache_size`` method; the
    no-recompile regression tests depend on it, so the private spelling
    lives HERE rather than at every call site."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:                                    # pragma: no cover
        raise NotImplementedError(
            "installed jax exposes no jit cache-size probe; update "
            "repro.compat.jit_cache_size for this version")
    return int(probe())


# ------------------------------------------------------------ cost analysis
def normalize_cost_analysis(ca: Any) -> Dict[str, float]:
    """Normalize a raw ``cost_analysis()`` return (a dict, or ``None`` on a
    backend without one) to a flat dict; an already-normalized record
    passes through."""
    if ca is None:
        return {}
    if not isinstance(ca, Mapping):                      # pragma: no cover
        raise TypeError(f"unexpected cost_analysis payload: {type(ca)!r}")
    return dict(ca)


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a plain dict."""
    return normalize_cost_analysis(compiled.cost_analysis())


# ------------------------------------------------------------- environment
def jax_api_report() -> Dict[str, Any]:
    """What the installed jax provides, surface by surface — consumed by
    ``scripts/check_env.py`` (fail fast) and useful for bug reports."""
    report: Dict[str, Any] = {"jax_version": jax.__version__,
                              "supported_jax": SUPPORTED_JAX}
    report["shard_map"] = hasattr(jax, "shard_map")
    report["set_mesh"] = hasattr(jax, "set_mesh")
    report["make_mesh"] = hasattr(jax, "make_mesh")
    report["all_to_all"] = hasattr(jax.lax, "all_to_all")
    report["ppermute"] = hasattr(jax.lax, "ppermute")
    try:
        from jax.experimental import pallas  # noqa: F401
        report["pallas"] = True
    except ImportError:                                  # pragma: no cover
        report["pallas"] = False
    return report


REQUIRED_APIS = ("shard_map", "set_mesh", "make_mesh", "all_to_all",
                 "ppermute", "pallas")


def missing_apis() -> list:
    """Names from ``REQUIRED_APIS`` the installed jax cannot satisfy."""
    r = jax_api_report()
    return [name for name in REQUIRED_APIS if not r[name]]
