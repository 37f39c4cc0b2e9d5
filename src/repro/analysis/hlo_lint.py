"""HLO/compiled-artifact lint engine (DESIGN.md §6).

The repo's performance story rests on invariants of the COMPILED artifact —
no filter-sized reduce in steady state, donated state aliased through every
scan carry, no per-call retrace, no host transfer inside the stream loop,
VMEM budgets on the fused kernels. They used to be guarded by ad-hoc regex
helpers in ``tests/test_hlo_step.py`` covering a handful of configs; this
module generalizes them into a pluggable rule registry that
``repro.analysis.entrypoints`` sweeps over every jitted hot path:

    Rule(name, doc, applies_to(entry) -> bool, check(Target) -> [Finding])

A ``Target`` wraps one entry point and lazily lowers/compiles it exactly
once, however many rules inspect it. Rules parse the post-optimization HLO
text — the artifact XLA will actually run — not the lowered StableHLO, so
what passes here is what executes. Findings carry a stable key
(``rule::entry-name``, no line numbers) so intentional exceptions can be
recorded in the checked-in baseline (``scripts/lint_baseline.json``) with
a one-line justification and survive recompiles.

Run the sweep: ``PYTHONPATH=src python -m repro.analysis`` (CLI wrapper:
``scripts/lint_hotpath.py``).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

# --------------------------------------------------------------- findings //


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation. ``where`` is the entry-point name (HLO rules) or
    ``path::token`` (source rules); the ``key`` is the stable identity the
    baseline suppresses — deliberately free of line numbers and shape
    digits so recompiles and unrelated edits do not churn it."""
    rule: str
    where: str
    detail: str

    @property
    def key(self) -> str:
        return f"{self.rule}::{self.where}"

    def to_dict(self) -> dict:
        return {"rule": self.rule, "where": self.where,
                "detail": self.detail, "key": self.key}


# ----------------------------------------------- HLO text parsing helpers //

# reduce-class ops in post-optimization HLO: "x = f32[] reduce(...)" /
# "reduce-window(...)" — operand shapes appear as dtype[d0,d1,...] in the args
_REDUCE_RE = re.compile(r"=\s*\S+\s+reduce(-window)?\(")
_SHAPE_RE = re.compile(r"\w+\[([0-9,]*)\]")
# parameter types in "entry_computation_layout={(u32[4,2048]{1,0}, ...)->..."
_PARAM_TYPE_RE = re.compile(r"[a-z]+\d*\[[\d,]*\]")
# "(u32[2,16384]{1,0}, s32[], ...) while(" — the loop-carried tuple type
_WHILE_RE = re.compile(r"=\s*\((.*?)\)\s+while\(")
_TYPED_SHAPE_RE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
_DTYPE_BYTES = {"pred": 1, "u8": 1, "s8": 1, "u16": 2, "s16": 2,
                "f16": 2, "bf16": 2, "u32": 4, "s32": 4, "f32": 4,
                "u64": 8, "s64": 8, "f64": 8, "c64": 8, "c128": 16}
# "{0}: (2, {}, may-alias)" entries inside input_output_alias={ ... }
_ALIAS_RE = re.compile(r"\{[\d,]*\}:\s*\((\d+),")

_HLO_DTYPE = {
    "uint8": "u8", "uint16": "u16", "uint32": "u32", "uint64": "u64",
    "int8": "s8", "int16": "s16", "int32": "s32", "int64": "s64",
    "bool": "pred", "float16": "f16", "bfloat16": "bf16",
    "float32": "f32", "float64": "f64",
}


# "%name = s32[2,1024]{1,0} op(" definitions and "(param_0.9: s32[2,32768],"
# computation parameters: the shape of every named value, for HLO text that
# prints operands by name only ("reduce-window(%param_0.9, %param_1.3)")
_DEF_RE = re.compile(r"%([\w.\-]+)\s*=\s*\w+\[([0-9,]*)\]")
_PARAM_SIG_RE = re.compile(r"([\w.\-]+):\s*\w+\[([0-9,]*)\]")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def reduce_operand_dims(hlo: str) -> List[int]:
    """Every dimension of every operand of every reduce-class op in the HLO
    text (the generalized ``tests/test_hlo_step.py`` helper). Operands are
    read from inline types where the text prints them, and otherwise looked
    up by name among the module's definitions and computation parameters
    (the XLA of jax 0.9.0 prints names only)."""
    shapes = {}
    for line in hlo.splitlines():
        for name, shape in _DEF_RE.findall(line):
            shapes[name] = shape
        if "->" in line and line.rstrip().endswith("{"):
            for name, shape in _PARAM_SIG_RE.findall(line.split("->", 1)[0]):
                shapes[name] = shape
    dims: List[int] = []
    for line in hlo.splitlines():
        m = _REDUCE_RE.search(line)
        if not m:
            continue
        call = line[m.end():].split(")", 1)[0]
        found = _SHAPE_RE.findall(call)
        found += [shapes.get(n, "") for n in _OPERAND_RE.findall(call)]
        for shape in found:
            if shape:
                dims.extend(int(d) for d in shape.split(","))
    return dims


def hlo_tuple_bytes(sig: str) -> int:
    """Total bytes of every typed shape in an HLO tuple-type string."""
    total = 0
    for dt, shape in _TYPED_SHAPE_RE.findall(sig):
        n = 1
        for d in shape.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def entry_io_bytes(compiled_hlo: str) -> Tuple[int, int]:
    """(parameter bytes, result bytes) of the entry computation, from
    ``entry_computation_layout`` — the artifact's declared I/O footprint."""
    params, results = _entry_signature(compiled_hlo)
    return hlo_tuple_bytes(params), hlo_tuple_bytes(results)


def entry_computation_text(compiled_hlo: str) -> str:
    """Body of the ``ENTRY`` computation only. Nested computations (fusion
    bodies, pallas-interpret grid loops) are excluded — their internal
    loops carry kernel-local buffers, not the scan state."""
    i = compiled_hlo.find("\nENTRY ")
    if i < 0:
        return compiled_hlo if compiled_hlo.startswith("ENTRY ") else ""
    lines = []
    for line in compiled_hlo[i + 1:].splitlines():
        lines.append(line)
        if line.rstrip() == "}":
            break
    return "\n".join(lines)


def while_carry_bytes(compiled_hlo: str) -> List[int]:
    """Carried-tuple bytes of every while op in the ENTRY computation —
    the scan loop's live footprint per iteration. While ops nested in
    fusion/kernel computations are deliberately not counted."""
    return [hlo_tuple_bytes(m.group(1))
            for m in _WHILE_RE.finditer(entry_computation_text(compiled_hlo))]


def _entry_signature(compiled_hlo: str) -> Tuple[str, str]:
    """(param-tuple text, result text) of ``entry_computation_layout`` —
    brace-balanced, since every type carries a ``{minor,major}`` layout."""
    sig = _brace_section(compiled_hlo, "entry_computation_layout={")
    if ")->" not in sig:
        return "", ""
    params, results = sig.split(")->", 1)
    return params, results


def entry_param_types(compiled_hlo: str) -> List[str]:
    """Parameter type strings (e.g. ``u32[4,2048]``) of the entry
    computation, in parameter order, from ``entry_computation_layout``."""
    return _PARAM_TYPE_RE.findall(_entry_signature(compiled_hlo)[0])


def _brace_section(text: str, anchor: str) -> str:
    """Text inside the brace-balanced section opened by ``anchor`` (which
    must end with ``{``); the alias table nests braces on one header line."""
    i = text.find(anchor)
    if i < 0:
        return ""
    j = i + len(anchor)
    depth, k = 1, j
    while k < len(text) and depth:
        if text[k] == "{":
            depth += 1
        elif text[k] == "}":
            depth -= 1
        k += 1
    return text[j:k - 1]


def aliased_param_indices(compiled_hlo: str) -> set:
    """Entry-parameter numbers that appear in the compiled module's
    ``input_output_alias`` table (donated buffers XLA updates in place)."""
    table = _brace_section(compiled_hlo, "input_output_alias={")
    return {int(m) for m in _ALIAS_RE.findall(table)}


def hlo_type(shape: Sequence[int], dtype: str) -> str:
    """The compiled-HLO type string for a leaf: ``('uint32', (4, 2048))`` ->
    ``u32[4,2048]``."""
    short = _HLO_DTYPE.get(str(dtype))
    if short is None:
        raise ValueError(f"no HLO spelling known for dtype {dtype!r}")
    return f"{short}[{','.join(str(int(d)) for d in shape)}]"


# ----------------------------------------------------------------- target //


class Target:
    """One entry point's compiled artifact, lowered/compiled lazily and at
    most once however many rules inspect it. Tests construct synthetic
    targets from raw HLO text via ``compiled_text=``/``lowered_text=`` to
    exercise rules without building a real entry."""

    def __init__(self, entry, *, compiled_text: Optional[str] = None,
                 lowered_text: Optional[str] = None):
        self.entry = entry
        self._lowered = None
        self._compiled = None
        self._lowered_text = lowered_text
        self._compiled_text = compiled_text

    def lowered(self):
        if self._lowered is None:
            self._lowered = self.entry.build()
        return self._lowered

    def lowered_text(self) -> str:
        if self._lowered_text is None:
            self._lowered_text = self.lowered().as_text()
        return self._lowered_text

    def compiled(self):
        if self._compiled is None:
            self._compiled = self.lowered().compile()
        return self._compiled

    def compiled_text(self) -> str:
        if self._compiled_text is None:
            self._compiled_text = self.compiled().as_text()
        return self._compiled_text


# ------------------------------------------------------------------ rules //


@dataclasses.dataclass(frozen=True)
class Rule:
    """One pluggable compiled-artifact invariant. ``applies_to`` gates on
    the entry point's tags/config (an inapplicable rule is neither a pass
    nor a failure); ``check`` inspects the Target and returns findings."""
    name: str
    doc: str
    applies_to: Callable[..., bool]
    check: Callable[[Target], List[Finding]]


HLO_RULES: Dict[str, Rule] = {}


def _register(rule: Rule) -> Rule:
    if rule.name in HLO_RULES:
        raise ValueError(f"duplicate rule {rule.name!r}")
    HLO_RULES[rule.name] = rule
    return rule


def _find(rule: str, where: str, detail: str) -> List[Finding]:
    return [Finding(rule, where, detail)]


# -- no-filter-sized-reduce ------------------------------------------------
# The paper's constant-per-element contract (DESIGN §3.1): steady-state
# load tracking is incremental, so the compiled step must not reduce over
# any buffer as large as the filter. Applies only when the entry's config
# separates the thresholds (filter well above every batch-event buffer).

def _reduce_applies(ep) -> bool:
    return bool(ep.extra.get("filter_elems")) and ep.extra.get("separable",
                                                              False)


def _reduce_check(t: Target) -> List[Finding]:
    w = t.entry.extra["filter_elems"]
    big = sorted({d for d in reduce_operand_dims(t.compiled_text())
                  if d >= w})
    if big:
        return _find("no-filter-sized-reduce", t.entry.name,
                     f"reduce over operand dims {big} >= filter size {w} "
                     f"— O(s) work crept into the steady-state path")
    return []


_register(Rule(
    "no-filter-sized-reduce",
    "compiled steady-state step must not reduce over any buffer as large "
    "as the filter (incremental load tracking, DESIGN §3.1)",
    _reduce_applies, _reduce_check))


# -- state-donated-and-aliased ---------------------------------------------
# Every donated state leaf — filter planes, position, load, rng, the swbf
# window ring, the elastic router table — must appear in the compiled
# module's input_output_alias table, or XLA is copying it per call/scan.

def _alias_applies(ep) -> bool:
    return "donated" in ep.tags and ep.leaves is not None


def _alias_check(t: Target) -> List[Finding]:
    leaves = list(t.entry.leaves())
    text = t.compiled_text()
    params = entry_param_types(text)
    aliased = aliased_param_indices(text)
    have: Dict[str, int] = {}
    for i in aliased:
        if i < len(params):
            have[params[i]] = have.get(params[i], 0) + 1
    missing = []
    for label, shape, dtype in leaves:
        ty = hlo_type(shape, dtype)
        if have.get(ty, 0) > 0:
            have[ty] -= 1
        else:
            missing.append(f"{label} ({ty})")
    if missing:
        return _find(
            "state-donated-and-aliased", t.entry.name,
            f"donated state leaves not in input_output_alias: "
            f"{', '.join(missing)} — XLA will copy them every call")
    return []


_register(Rule(
    "state-donated-and-aliased",
    "every donated state leaf (filter/plane/ring/router) must be aliased "
    "in place in the compiled module (DESIGN §3.5)",
    _alias_applies, _alias_check))


# -- no-scan-carry-copy ----------------------------------------------------
# The PR-4 trap: a scan carry that is dynamic-sliced AND updated in the
# same body makes XLA move O(window·s) words per batch — the inflated
# carry is the trap's robust static signature (raw copy-op counting is
# too noisy in optimized HLO: hoisted memsets and fusion-internal layout
# copies appear in accepted-good streams). The compiled while loop's
# carried tuple must stay within the entry's DECLARED I/O footprint
# (params + results, measured 0.5-1.0x across every good stream) plus
# slack; an expanded plane-stack ring blows it by the window factor.

_CARRY_SLACK_BYTES = 64 * 1024


def _carry_applies(ep) -> bool:
    return "stream" in ep.tags


def _carry_check(t: Target) -> List[Finding]:
    text = t.compiled_text()
    params, results = entry_io_bytes(text)
    budget = params + results + _CARRY_SLACK_BYTES
    worst = max(while_carry_bytes(text), default=0)
    if worst > budget:
        return _find(
            "no-scan-carry-copy", t.entry.name,
            f"scan carry of {worst} B exceeds the declared I/O footprint "
            f"{params}+{results} B (+{_CARRY_SLACK_BYTES} slack) — the "
            f"loop is carrying/copying buffers beyond the donated state "
            f"(the PR-4 slice+update ring trap)")
    return []


_register(Rule(
    "no-scan-carry-copy",
    "the stream scan's while-loop carry stays within the declared entry "
    "I/O footprint — no O(window*s) inflated/copied carry (the PR-4 "
    "dynamic-slice+update trap, DESIGN §3.7)",
    _carry_applies, _carry_check))


# -- no-host-transfer-in-scan ----------------------------------------------

_HOST_TOKENS = ("infeed", "outfeed", " send(", " send-start(",
                " recv(", " recv-start(", "callback")


def _host_check(t: Target) -> List[Finding]:
    text = t.compiled_text()
    hits = sorted({tok.strip(" (") for tok in _HOST_TOKENS if tok in text})
    if hits:
        return _find(
            "no-host-transfer-in-scan", t.entry.name,
            f"host-transfer ops in the compiled module: {hits} — a device "
            f"sync inside the hot path serializes the stream")
    return []


_register(Rule(
    "no-host-transfer-in-scan",
    "no infeed/outfeed/send/recv/host-callback inside a compiled hot "
    "path — metrics are read out device-side (DESIGN §6)",
    lambda ep: True, _host_check))


# -- no-f64-upcast ---------------------------------------------------------

def _f64_check(t: Target) -> List[Finding]:
    n = len(re.findall(r"\bf64\[|\bc128\[", t.compiled_text()))
    if n:
        return _find(
            "no-f64-upcast", t.entry.name,
            f"{n} f64/c128-typed values in the compiled module — a Python "
            f"float or np.float64 leaked into the traced math")
    return []


_register(Rule(
    "no-f64-upcast",
    "compiled hot paths carry no float64/complex128 values (accelerator "
    "f64 is emulated and slow; the repo's math is int/f32)",
    lambda ep: True, _f64_check))


# -- single-dispatch-no-retrace --------------------------------------------

def _retrace_check(t: Target) -> List[Finding]:
    problems = t.entry.retrace_probe()
    return [Finding("single-dispatch-no-retrace", t.entry.name, p)
            for p in problems]


_register(Rule(
    "single-dispatch-no-retrace",
    "repeating the same-shaped call must reuse one compiled "
    "specialization (compile-cache probe, DESIGN §3.5)",
    lambda ep: ep.retrace_probe is not None, _retrace_check))


# -- pallas-vmem-budget ----------------------------------------------------
# Static mirror of the trace-time check_vmem_budget guard: recompute the
# fused step's resident working set from the config alone, so over-budget
# configs are findings (not trace-time ValueErrors) and the sweep needs no
# kernel trace to audit the budget.

def _vmem_applies(ep) -> bool:
    return ep.cfg is not None and getattr(ep.cfg, "backend", None) == "pallas"


def _vmem_check(t: Target) -> List[Finding]:
    from ..kernels.common import VMEM_FILTER_BYTES_LIMIT, fused_resident_bytes
    nbytes = fused_resident_bytes(t.entry.cfg)
    if nbytes > VMEM_FILTER_BYTES_LIMIT:
        return _find(
            "pallas-vmem-budget", t.entry.name,
            f"fused-step working set {nbytes} B exceeds the "
            f"{VMEM_FILTER_BYTES_LIMIT} B VMEM budget — shard the filter "
            f"(repro.dedup.sharded) first")
    return []


_register(Rule(
    "pallas-vmem-budget",
    "the fused kernel's VMEM-resident working set stays within "
    "kernels.common.VMEM_FILTER_BYTES_LIMIT, checked statically from the "
    "config (DESIGN §3.4)",
    _vmem_applies, _vmem_check))


# ----------------------------------------------------------------- driver //


def resolve_rules(rules=None) -> List[Rule]:
    """Normalize a rule selection (None = all, else names or Rule objects)."""
    if rules is None:
        return list(HLO_RULES.values())
    out = []
    for r in rules:
        out.append(HLO_RULES[r] if isinstance(r, str) else r)
    return out


def lint_entry(entry, rules=None, *, target: Optional[Target] = None
               ) -> List[Finding]:
    """Run every applicable rule against one entry point. A rule that
    raises becomes a ``lint-error`` finding (a hot path that cannot even be
    lowered is itself a violation worth surfacing, not a crash)."""
    target = Target(entry) if target is None else target
    findings: List[Finding] = []
    for rule in resolve_rules(rules):
        try:
            if not rule.applies_to(entry):
                continue
            findings.extend(rule.check(target))
        except Exception as e:  # noqa: BLE001 — surface, don't crash the sweep
            findings.append(Finding(
                "lint-error", f"{entry.name}::{rule.name}",
                f"{type(e).__name__}: {e}"))
    return findings
