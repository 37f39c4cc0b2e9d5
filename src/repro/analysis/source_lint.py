"""Source/AST lint engine: repo conventions, enforced statically
(DESIGN.md §6).

The compiled-artifact rules (``hlo_lint``) prove the artifact has the
right shape; these rules prove the SOURCE keeps the conventions that make
that true as the code grows:

  * ``compat-choke-point`` — version-sensitive JAX surfaces (shard_map,
    set_mesh, ppermute, ``compiled.cost_analysis``, the jit cache probe)
    are only touched through ``repro/compat.py`` (DESIGN §4.3), so a JAX
    upgrade is one file's diff, not a repo-wide hunt.
  * ``no-host-sync-in-hot-path`` — ``block_until_ready``/``device_get``/
    ``.item()``/``np.asarray``/``jax.debug.*`` in a HOT module is a device
    sync serializing the stream; metrics are read out in
    ``dedup/metrics.py`` (deliberately outside the hot set).
  * ``no-deprecated-shim-import`` — ``kernels/fused_step.py`` and
    ``fused_counter_step.py`` are deprecation shims; new src code imports
    ``kernels.fused_template``.
  * ``no-python-branch-on-tracer`` — an ``if``/``while`` on a local
    assigned from a jnp/lax/random call inside a hot module is a trace
    error (or silent concretization) waiting to happen. Heuristic: names
    re-bound to host values are not tracked through control flow.

Pure stdlib (ast + os) — importable and runnable without jax, so the
source sweep stays fast and works in any environment.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Callable, Dict, Iterable, List, Optional, Sequence

from .hlo_lint import Finding

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
SRC_ROOT = os.path.join(REPO_ROOT, "src", "repro")

# modules on the per-element dispatch path — a host sync here serializes
# the stream; dedup/metrics.py is deliberately NOT in this set (it is THE
# sanctioned read-out point, DESIGN §7)
HOT_MODULES = (
    "core/batched.py", "core/packed.py", "core/engine.py",
    "core/hashing.py", "core/sketch.py", "core/state.py",
    "dedup/sharded.py", "dedup/pipeline.py", "kernels/",
)

# drifted / version-sensitive surfaces: any dotted use outside compat.py
# is a violation (the suffix match catches every import spelling)
DRIFTED_SUFFIXES = (
    "jax.experimental.shard_map", "shard_map.shard_map", "jax.shard_map",
    "jax.set_mesh", "jax.sharding.set_mesh", "jax.sharding.use_mesh",
    "jax.make_mesh",
    "lax.ppermute", "lax.pshuffle",
    ".cost_analysis", "._cache_size",
)
COMPAT_EXEMPT = ("compat.py",)

SHIM_MODULES = ("fused_step", "fused_counter_step")
SHIM_EXEMPT = ("kernels/fused_step.py", "kernels/fused_counter_step.py")

HOST_SYNC_ATTRS = ("block_until_ready", "device_get", "item")
NUMPY_SYNC_ATTRS = ("asarray", "array")

TRACED_CALL_PREFIXES = ("jnp.", "jax.numpy.", "jax.lax.", "lax.",
                        "jax.random.")


@dataclasses.dataclass(frozen=True)
class SourceRule:
    """One source convention. ``check(relpath, tree, text, hot)`` returns
    findings; ``hot`` says whether the file is on the hot-path set."""
    name: str
    doc: str
    check: Callable[[str, ast.AST, str, bool], List[Finding]]


SOURCE_RULES: Dict[str, SourceRule] = {}


def _register(rule: SourceRule) -> SourceRule:
    if rule.name in SOURCE_RULES:
        raise ValueError(f"duplicate rule {rule.name!r}")
    SOURCE_RULES[rule.name] = rule
    return rule


# ------------------------------------------------------------- ast helpers


def dotted_name(node: ast.AST) -> Optional[str]:
    """``jax.lax.ppermute`` from the Attribute chain, None if the root is
    not a plain Name."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _numpy_aliases(tree: ast.AST) -> set:
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "numpy":
                    out.add(alias.asname or "numpy")
    return out


# ------------------------------------------------------------------- rules


def _check_compat(relpath: str, tree: ast.AST, text: str, hot: bool
                  ) -> List[Finding]:
    if relpath.replace(os.sep, "/").endswith(COMPAT_EXEMPT):
        return []
    findings = []
    for node in ast.walk(tree):
        dotted = None
        if isinstance(node, ast.ImportFrom) and node.module:
            for alias in node.names:
                full = f"{node.module}.{alias.name}"
                if any(full == s or full.endswith(s)
                       for s in DRIFTED_SUFFIXES):
                    findings.append(Finding(
                        "compat-choke-point", f"{relpath}::{full}",
                        f"line {node.lineno}: `from {node.module} import "
                        f"{alias.name}` — route through repro.compat "
                        f"(DESIGN §4.3)"))
        elif isinstance(node, ast.Attribute):
            dotted = dotted_name(node)
        if dotted and any(dotted == s or dotted.endswith(s)
                          for s in DRIFTED_SUFFIXES):
            findings.append(Finding(
                "compat-choke-point", f"{relpath}::{dotted}",
                f"line {node.lineno}: `{dotted}` — version-sensitive "
                f"surface, route through repro.compat (DESIGN §4.3)"))
    return findings


_register(SourceRule(
    "compat-choke-point",
    "version-sensitive JAX surfaces are only touched through "
    "repro/compat.py (DESIGN §4.3)",
    _check_compat))


def _check_host_sync(relpath: str, tree: ast.AST, text: str, hot: bool
                     ) -> List[Finding]:
    if not hot:
        return []
    np_aliases = _numpy_aliases(tree)
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if isinstance(fn, ast.Attribute):
            dotted = dotted_name(fn) or f"?.{fn.attr}"
            root = dotted.split(".", 1)[0]
            if fn.attr in HOST_SYNC_ATTRS:
                findings.append(Finding(
                    "no-host-sync-in-hot-path", f"{relpath}::{dotted}",
                    f"line {node.lineno}: `{dotted}()` forces a device "
                    f"sync in a hot module — read out via "
                    f"dedup/metrics.py instead (DESIGN §7)"))
            elif root in np_aliases and fn.attr in NUMPY_SYNC_ATTRS:
                findings.append(Finding(
                    "no-host-sync-in-hot-path", f"{relpath}::{dotted}",
                    f"line {node.lineno}: `{dotted}(...)` on a device "
                    f"value copies to host in a hot module (DESIGN §7)"))
            elif dotted.startswith("jax.debug.") or \
                    dotted.endswith("debug.print") or \
                    dotted.endswith("debug.callback"):
                findings.append(Finding(
                    "no-host-sync-in-hot-path", f"{relpath}::{dotted}",
                    f"line {node.lineno}: `{dotted}` inserts a host "
                    f"callback into the compiled hot path (DESIGN §7)"))
    return findings


_register(SourceRule(
    "no-host-sync-in-hot-path",
    "no block_until_ready/device_get/.item()/np.asarray/jax.debug.* in "
    "hot modules — metrics read out device-side (DESIGN §7)",
    _check_host_sync))


def _check_shim_import(relpath: str, tree: ast.AST, text: str, hot: bool
                       ) -> List[Finding]:
    rel = relpath.replace(os.sep, "/")
    if rel.endswith(SHIM_EXEMPT):
        return []
    findings = []
    for node in ast.walk(tree):
        mod = None
        if isinstance(node, ast.ImportFrom) and node.module:
            mod = node.module
        elif isinstance(node, ast.Import):
            mod = ",".join(a.name for a in node.names)
        if mod and any(s in mod for s in SHIM_MODULES):
            findings.append(Finding(
                "no-deprecated-shim-import", f"{relpath}::{mod}",
                f"line {node.lineno}: imports deprecated kernel shim "
                f"`{mod}` — use kernels.fused_template (DESIGN §3.8)"))
    return findings


_register(SourceRule(
    "no-deprecated-shim-import",
    "src code imports kernels.fused_template, not the fused_step/"
    "fused_counter_step deprecation shims (DESIGN §3.8)",
    _check_shim_import))


# attribute reads that are static under tracing — branching on them is fine
STATIC_ATTRS = ("shape", "ndim", "dtype", "size", "aval", "sharding",
                "weak_type")


def _value_names(test: ast.AST) -> List[ast.Name]:
    """Name nodes whose VALUE the branch test consumes: identity checks
    (``x is None``) and static-attribute reads (``x.shape[0]``) do not
    concretize a tracer and are skipped."""
    if isinstance(test, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
        return []
    out: List[ast.Name] = []

    def rec(n: ast.AST):
        if isinstance(n, ast.Attribute) and n.attr in STATIC_ATTRS:
            return
        if isinstance(n, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in n.ops):
            return
        if isinstance(n, ast.Name):
            out.append(n)
        for c in ast.iter_child_nodes(n):
            rec(c)
    rec(test)
    return out


def _check_tracer_branch(relpath: str, tree: ast.AST, text: str, hot: bool
                         ) -> List[Finding]:
    if not hot:
        return []
    findings = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        traced: set = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                name = node.targets[0].id
                val = node.value
                if isinstance(val, ast.Call):
                    dotted = dotted_name(val.func) or ""
                    if dotted.startswith(TRACED_CALL_PREFIXES):
                        traced.add(name)
                        continue
                # any other re-binding makes the name host-valued again
                traced.discard(name)
        if not traced:
            continue
        for node in ast.walk(fn):
            if not isinstance(node, (ast.If, ast.While)):
                continue
            kind = "if" if isinstance(node, ast.If) else "while"
            for leaf in _value_names(node.test):
                if leaf.id in traced:
                    findings.append(Finding(
                        "no-python-branch-on-tracer",
                        f"{relpath}::{fn.name}/{leaf.id}",
                        f"line {node.lineno}: Python `{kind}` on "
                        f"`{leaf.id}`, which is assigned from a traced "
                        f"jnp/lax call in `{fn.name}` — branches on "
                        f"tracers fail (or silently sync) under jit"))
                    break
    return findings


_register(SourceRule(
    "no-python-branch-on-tracer",
    "no Python if/while on locals assigned from jnp/lax/random calls in "
    "hot modules (heuristic)",
    _check_tracer_branch))


# ------------------------------------------------------------------ driver


def _iter_src_files() -> Iterable[str]:
    for dirpath, _dirs, files in os.walk(SRC_ROOT):
        for name in sorted(files):
            if name.endswith(".py"):
                yield os.path.join(dirpath, name)


def _relpath(path: str) -> str:
    rel = os.path.relpath(os.path.abspath(path), REPO_ROOT)
    if rel.startswith(".."):
        rel = os.path.basename(path)
    return rel.replace(os.sep, "/")


def is_hot(relpath: str) -> bool:
    rel = relpath.replace(os.sep, "/")
    for mod in HOT_MODULES:
        tail = f"repro/{mod}"
        if mod.endswith("/"):
            if f"/{tail}" in f"/{rel}":
                return True
        elif rel.endswith(tail):
            return True
    return False


def lint_sources(paths: Optional[Sequence[str]] = None,
                 rules: Optional[Sequence[str]] = None,
                 hot: Optional[bool] = None) -> List[Finding]:
    """Sweep ``src/repro`` (or explicit ``paths``) with every source rule.
    ``hot`` overrides hot-module classification (tests pass hot=True to
    run the hot-only rules against a scratch file)."""
    selected = ([SOURCE_RULES[r] for r in rules] if rules is not None
                else list(SOURCE_RULES.values()))
    findings: List[Finding] = []
    for path in (paths if paths is not None else _iter_src_files()):
        rel = _relpath(path)
        with open(path, errors="replace") as f:
            text = f.read()
        try:
            tree = ast.parse(text)
        except SyntaxError as e:
            findings.append(Finding("lint-error", rel,
                                    f"SyntaxError: {e}"))
            continue
        file_hot = is_hot(rel) if hot is None else hot
        for rule in selected:
            findings.extend(rule.check(rel, tree, text, file_hot))
    return findings
