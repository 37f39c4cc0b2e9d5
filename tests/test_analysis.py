"""The linter lints itself (DESIGN.md §6): every rule must FIRE on a
deliberately-broken mini — a rule that cannot catch its own trap is dead
weight — and the sweep plumbing (baseline split, stale detection, CLI
filters, finding keys) must behave.

HLO parsing rules are exercised twice: against synthetic HLO text (fast,
pins the exact textual contract) and, where cheap, against a real broken
entry (pins that jax still emits text the parsers understand)."""

import json
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import (
    HLO_RULES, SOURCE_RULES, Finding, Target, entry_computation_text,
    entry_io_bytes, hlo_tuple_bytes, lint_entry, lint_sources, load_baseline,
    reduce_operand_dims, run_lint, while_carry_bytes,
)
from repro.analysis.entrypoints import (
    CANON_BATCH, EntryPoint, _canon_cfg, get_entry, iter_entry_points,
    step_entry, stream_entry,
)
from repro.analysis.runner import render
from repro.core.config import DedupConfig

SMALL = dict(memory_bits=1 << 14, batch_size=128)


def _fake_entry(name="fake", tags=(), cfg=None, extra=None, probe=None):
    return EntryPoint(name=name, tags=frozenset(tags), cfg=cfg,
                      build=lambda: (_ for _ in ()).throw(
                          AssertionError("synthetic target must not build")),
                      retrace_probe=probe, extra=dict(extra or {}))


# ------------------------------------------------------------ HLO helpers //


def test_hlo_tuple_bytes():
    assert hlo_tuple_bytes("u32[4,2048]{1,0}, pred[8]{0}, s32[]") \
        == 4 * 2048 * 4 + 8 + 4


def test_entry_computation_text_excludes_nested_computations():
    hlo = textwrap.dedent("""\
        HloModule jit_x

        %fused_computation.1 (p: u32[9]) -> u32[9] {
          %w1 = (s32[], u32[999999]{0}) while((s32[], u32[999999]{0}) %t)
        }

        ENTRY %main.1 (a: u32[4]) -> u32[4] {
          %w2 = (s32[], u32[8]{0}) while((s32[], u32[8]{0}) %t2)
          ROOT %r = u32[4]{0} copy(%a)
        }
        """)
    assert "while" in entry_computation_text(hlo)
    assert "999999" not in entry_computation_text(hlo)
    assert while_carry_bytes(hlo) == [4 + 32]


# ------------------------------------------- each rule fires on its trap //


def test_no_filter_sized_reduce_fires_on_debug_exact_load():
    """The canonical broken mini is real: debug_exact_load compiles an O(s)
    reduce and the rule reports it (the sweep suppresses this exact key in
    scripts/lint_baseline.json)."""
    ep = get_entry("step/rlbsbf/planes/jnp/debug-exact-load")
    found = lint_entry(ep, rules=["no-filter-sized-reduce"])
    assert [f.key for f in found] == [
        "no-filter-sized-reduce::step/rlbsbf/planes/jnp/debug-exact-load"]


def test_donation_rule_fires_on_undonated_stream():
    """stream_entry(donate=False) is the deliberately-broken twin: same
    scan, state NOT donated, so no alias table entry covers the filter."""
    cfg = DedupConfig.for_variant("rlbsbf", **SMALL)
    broken = stream_entry(cfg, donate=False)
    assert "donated" not in broken.tags      # rule would not apply...
    found = HLO_RULES["state-donated-and-aliased"].check(Target(broken))
    assert found and ".bits" in found[0].detail
    # ...and the applicability gate keeps lint_entry quiet about it
    assert lint_entry(broken, rules=["state-donated-and-aliased"]) == []


def test_scan_carry_rule_fires_on_inflated_carry():
    """Synthetic HLO with a while carry far above the declared I/O — the
    PR-4 slice+update ring trap's static signature."""
    hlo = textwrap.dedent("""\
        HloModule jit_s, entry_computation_layout={(u32[256]{0})->u32[256]{0}}

        ENTRY %main.1 (a: u32[256]) -> u32[256] {
          %w = (s32[], u32[4,262144]{1,0}) while((s32[], u32[4,262144]{1,0}) %t)
        }
        """)
    ep = _fake_entry("mini/stream", tags=("stream",))
    found = lint_entry(ep, rules=["no-scan-carry-copy"],
                       target=Target(ep, compiled_text=hlo))
    assert [f.rule for f in found] == ["no-scan-carry-copy"]
    assert "4194308" in found[0].detail      # the inflated carry, in bytes


def test_scan_carry_rule_ignores_kernel_internal_loops():
    """A fusion-internal grid loop (pallas interpret) may carry big local
    buffers — only the ENTRY computation's while is the scan."""
    hlo = textwrap.dedent("""\
        HloModule jit_s, entry_computation_layout={(u32[256]{0})->u32[256]{0}}

        %fused_computation.9 (p: u32[9]) -> u32[9] {
          %w1 = (s32[], u32[4,262144]{1,0}) while((s32[], u32[4,262144]{1,0}) %t)
        }

        ENTRY %main.1 (a: u32[256]) -> u32[256] {
          %w2 = (s32[], u32[256]{0}) while((s32[], u32[256]{0}) %t2)
        }
        """)
    ep = _fake_entry("mini/stream", tags=("stream",))
    assert lint_entry(ep, rules=["no-scan-carry-copy"],
                      target=Target(ep, compiled_text=hlo)) == []


def test_host_transfer_rule_fires_on_callback():
    hlo = "ENTRY %m {\n  %cc = u32[] custom-call(), custom_call_target=\"xla_ffi_python_cpu_callback\"\n}"
    ep = _fake_entry("mini/host")
    found = lint_entry(ep, rules=["no-host-transfer-in-scan"],
                       target=Target(ep, compiled_text=hlo))
    assert [f.rule for f in found] == ["no-host-transfer-in-scan"]


def test_f64_rule_fires_on_double():
    hlo = "ENTRY %m {\n  %c = f64[128]{0} convert(%x)\n}"
    ep = _fake_entry("mini/f64")
    found = lint_entry(ep, rules=["no-f64-upcast"],
                       target=Target(ep, compiled_text=hlo))
    assert [f.rule for f in found] == ["no-f64-upcast"]


def test_retrace_rule_reports_probe_problems():
    ep = _fake_entry("mini/retrace", probe=lambda: ["grew the cache 1 -> 3"])
    found = lint_entry(ep, rules=["single-dispatch-no-retrace"],
                       target=Target(ep, compiled_text=""))
    assert [f.rule for f in found] == ["single-dispatch-no-retrace"]
    assert "1 -> 3" in found[0].detail


def test_vmem_rule_fires_statically_on_oversized_pallas_cfg():
    """No trace, no kernel build: the budget is recomputed from the config
    alone, so an over-VMEM config is a finding, not a trace-time error."""
    cfg = DedupConfig.for_variant(
        "rlbsbf", memory_bits=1 << 27, batch_size=128, backend="pallas",
        layout="planes")
    ep = _fake_entry("mini/vmem", cfg=cfg)
    found = lint_entry(ep, rules=["pallas-vmem-budget"],
                       target=Target(ep, compiled_text=""))
    assert [f.rule for f in found] == ["pallas-vmem-budget"]
    assert "shard the filter" in found[0].detail


def test_rule_exception_becomes_lint_error_finding():
    ep = _fake_entry("mini/crash")
    found = lint_entry(ep, rules=["no-f64-upcast"])   # build() raises
    assert [f.rule for f in found] == ["lint-error"]
    assert "mini/crash::no-f64-upcast" == found[0].where


# ----------------------------------------------------------- source rules //


def _lint_snippet(tmp_path, src, hot=True):
    p = tmp_path / "snippet.py"
    p.write_text(textwrap.dedent(src))
    return lint_sources([str(p)], hot=hot)


def test_source_rule_compat_choke_point(tmp_path):
    found = _lint_snippet(tmp_path, """\
        from jax.experimental.shard_map import shard_map
        def f(c):
            return c.cost_analysis()
        """, hot=False)
    assert {f.rule for f in found} == {"compat-choke-point"}
    assert len(found) == 2


def test_source_rule_host_sync_only_in_hot(tmp_path):
    src = """\
        import numpy as np
        def f(x):
            np.asarray(x)
            return x.block_until_ready()
        """
    hot = _lint_snippet(tmp_path, src, hot=True)
    assert {f.rule for f in hot} == {"no-host-sync-in-hot-path"}
    assert len(hot) == 2
    assert _lint_snippet(tmp_path, src, hot=False) == []


def test_source_rule_shim_import(tmp_path):
    found = _lint_snippet(tmp_path, """\
        from repro.kernels.fused_step import make_fused_step
        """, hot=False)
    assert [f.rule for f in found] == ["no-deprecated-shim-import"]


def test_source_rule_tracer_branch(tmp_path):
    found = _lint_snippet(tmp_path, """\
        import jax.numpy as jnp
        def f(x):
            y = jnp.sum(x)
            if y > 0:
                return x
            return -x
        """)
    assert [f.rule for f in found] == ["no-python-branch-on-tracer"]
    assert "`y`" in found[0].detail


def test_source_rule_tracer_branch_skips_safe_idioms(tmp_path):
    """is-None defaults, static .shape reads and host re-bindings must not
    fire — these are the three false-positive families found in the repo."""
    assert _lint_snippet(tmp_path, """\
        import jax.numpy as jnp
        def f(x, valid=None):
            v = jnp.ones_like(x)
            if valid is None:
                valid = v
            if x.shape[0] > 4:
                return valid
            n = jnp.sum(x)
            n = int(3)
            while n > 0:
                n -= 1
            return valid
        """) == []


def test_repo_source_sweep_matches_baseline():
    """The checked-in tree carries exactly the baselined source findings:
    the two deliberate shim re-exports in kernels/__init__.py."""
    keys = sorted(f.key for f in lint_sources())
    assert keys == [
        "no-deprecated-shim-import::src/repro/kernels/__init__.py"
        "::fused_counter_step",
        "no-deprecated-shim-import::src/repro/kernels/__init__.py"
        "::fused_step",
    ]


# --------------------------------------------------------------- plumbing //


def test_entry_matrix_shape():
    eps = iter_entry_points()
    names = [ep.name for ep in eps]
    assert len(names) == len(set(names))           # names are unique keys
    assert len(names) >= 30
    # enumeration is lazy: nothing above traced or compiled anything
    for prefix in ("step/rlbsbf/planes/jnp", "step/rlbsbf/planes/pallas",
                   "stream/rlbsbf/planes/jnp", "sharded-stream/static",
                   "serving/process-padded"):
        assert any(n.startswith(prefix) for n in names), prefix
    for ep in eps:
        if ep.extra.get("filter_elems"):
            assert ep.extra["separable"], (
                f"{ep.name}: canonical config does not separate the lint "
                f"thresholds — shrink CANON_BATCH or grow the filter")


def test_entry_io_bytes_on_real_step():
    ep = step_entry(_canon_cfg("rlbsbf", "planes"))
    params, results = entry_io_bytes(Target(ep).compiled_text())
    # params carry at least the keys batch (u32) plus the filter words
    assert params > 4 * CANON_BATCH + ep.cfg.k * ep.cfg.s_words * 4
    assert results > 0


def test_baseline_split_and_stale(tmp_path):
    base = tmp_path / "baseline.json"
    base.write_text(json.dumps({"suppressions": [
        {"key": "no-deprecated-shim-import::src/repro/kernels/"
                "__init__.py::fused_step", "reason": "kept on purpose"},
        {"key": "ghost-rule::nowhere", "reason": "stale on purpose"},
    ]}))
    report = run_lint(do_hlo=False, baseline=load_baseline(str(base)))
    assert [f.key for f, _ in report.suppressed] == [
        "no-deprecated-shim-import::src/repro/kernels/__init__.py"
        "::fused_step"]
    assert report.stale_baseline == ["ghost-rule::nowhere"]
    assert [f.rule for f in report.findings] == ["no-deprecated-shim-import"]
    text = render(report)
    assert "FAIL" in text and "stale baseline" in text
    assert report.to_dict()["ok"] is False


def test_stale_baseline_fails_full_sweep_only():
    """A stale suppression FAILS a full sweep (the justification now
    misleads); a filtered sweep downgrades it to a warning, since a
    narrowed sweep cannot tell stale from unswept (DESIGN §6)."""
    import dataclasses
    from repro.analysis.runner import LintReport
    rep = LintReport(findings=[], suppressed=[],
                     stale_baseline=["ghost-rule::nowhere"],
                     n_entries=1, n_hlo_rules=1, n_source_rules=1,
                     n_source_files=1, elapsed_s=0.0, partial=False)
    assert rep.ok is False and rep.to_dict()["ok"] is False
    text = render(rep)
    assert "FAIL" in text and "stale baseline suppression" in text
    filt = dataclasses.replace(rep, partial=True)
    assert filt.ok is True
    assert "WARNING" in render(filt)


def test_baseline_requires_justification(tmp_path):
    base = tmp_path / "baseline.json"
    base.write_text(json.dumps({"suppressions": [{"key": "x::y"}]}))
    with pytest.raises(ValueError, match="justification"):
        load_baseline(str(base))


def test_finding_key_is_stable():
    f = Finding("r", "entry/x", "line 12: something, 4096 bytes")
    assert f.key == "r::entry/x"            # no digits from the detail
    assert f.to_dict()["key"] == f.key


def test_cli_source_only_respects_baseline():
    """End to end through the module CLI: the checked-in baseline makes the
    source-only sweep exit 0; an empty baseline makes it exit 1."""
    ok = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--source-only", "-q"],
        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "lint_hotpath: OK" in ok.stdout
    bad = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--source-only", "-q",
         "--baseline", "none", "--json", "-"],
        capture_output=True, text=True)
    assert bad.returncode == 1
    payload = json.loads(bad.stdout)
    assert payload["ok"] is False and len(payload["findings"]) == 2


def test_cli_list_names_every_rule():
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--list"],
        capture_output=True, text=True)
    assert out.returncode == 0
    for name in list(HLO_RULES) + list(SOURCE_RULES):
        assert name in out.stdout


# ------------------------------------------------- vmem formula cross-check //


def test_fused_resident_bytes_matches_kernel_formula(monkeypatch):
    """The static budget mirror must agree with the trace-time guard's
    arithmetic for both families (bitset: k filter rows plus 2k delta rows,
    or per-row event operands under kernel accumulation; counter: d-plane
    words plus delta planes or event operands)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.kernels import fused_template
    from repro.kernels.common import (VMEM_FILTER_BYTES_LIMIT,
                                      counter_vmem_words,
                                      fused_resident_bytes)
    from repro.core.state import init_state
    seen = []
    monkeypatch.setattr(fused_template, "check_vmem_budget",
                        lambda nbytes, what: seen.append(nbytes))
    for variant in ("rlbsbf", "sbf", "cms"):
        for acc in (False, True):
            cfg = dataclasses.replace(
                _canon_cfg(variant, "planes", backend="pallas"),
                kernel_accumulate=acc)
            step = fused_template.make_fused_step(cfg)
            b = cfg.batch_size
            seen.clear()
            jax.eval_shape(step, init_state(cfg),
                           jax.ShapeDtypeStruct((b,), jnp.uint32),
                           jax.ShapeDtypeStruct((b,), jnp.bool_))
            assert seen == [fused_resident_bytes(cfg)], (variant, acc)
    bit = _canon_cfg("rlbsbf", "planes", backend="pallas")
    assert fused_resident_bytes(bit) == 3 * bit.k * bit.s_words * 4
    cnt = _canon_cfg("sbf", "planes", backend="pallas")
    words = counter_vmem_words(cnt.n_planes, has_sub=True, set_mode=True,
                               accumulate=cnt.kernel_accumulate)
    assert fused_resident_bytes(cnt) >= words * cnt.s_words * 4
    # every canonical pallas entry fits the budget (the sweep relies on it)
    for ep in iter_entry_points():
        if ep.cfg is not None and ep.cfg.backend == "pallas":
            assert fused_resident_bytes(ep.cfg) <= VMEM_FILTER_BYTES_LIMIT
