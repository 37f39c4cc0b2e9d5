"""HLO-level contracts of the batched hot path (DESIGN.md §3, §6):

  * steady-state step for rlbsbf packed contains NO O(s) popcount/reduce over
    the filter buffer — load is tracked incrementally from scatter pre-values;
  * the donated filter state is aliased in place by the stream scan;
  * repeated ``run_stream`` calls reuse the cached compiled scan (no
    re-trace/re-compile per invocation).

These invariants are enforced repo-wide by ``repro.analysis`` (the
``python -m repro.analysis`` sweep over every entry point); the tests here
pin the ORIGINAL acceptance configs — larger than the sweep's canonical
sizes — through the same rule engine, so the rules and the historical bars
can never drift apart.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import lint_entry, reduce_operand_dims
from repro.analysis.entrypoints import _canon_cfg, step_entry, stream_entry
from repro.analysis.hlo_lint import Target
from repro.core import Dedup, DedupConfig
from repro.core.engine import get_engine

CFG = dict(memory_bits=1 << 21, batch_size=8192, packed=True)


def _step_target(cfg):
    return step_entry(cfg)


def test_no_filter_sized_reduce_in_steady_state_step():
    """The acceptance bar: compiled rlbsbf-packed step must not reduce over
    any buffer as large as the filter (W words per row)."""
    cfg = DedupConfig.for_variant("rlbsbf", **CFG)
    ep = _step_target(cfg)
    assert ep.extra["separable"]       # thresholds separated by construction
    assert lint_entry(ep, rules=["no-filter-sized-reduce"]) == []


def test_canonical_bitset_step_and_stream_make_no_filter_sized_pass():
    """The sweep's canonical 2^20-bit bitset step (undonated: it may copy
    its input once) and its donated stream update only the touched words
    in place: no zero-filled delta, no relayout, no elementwise combine
    (DESIGN §3.2)."""
    cfg = _canon_cfg("rlbsbf", "planes")
    assert cfg.memory_bits == 1 << 20
    for ep in (step_entry(cfg), stream_entry(cfg)):
        assert lint_entry(ep, rules=["no-filter-sized-pass"]) == [], ep.name


def test_debug_exact_load_does_popcount_reduce():
    """Sanity of the detector: the escape hatch DOES reduce over the filter,
    and the rule fires on it (this is the finding the checked-in baseline
    suppresses for the sweep's canonical debug entry)."""
    cfg = DedupConfig.for_variant("rlbsbf", debug_exact_load=True, **CFG)
    found = lint_entry(_step_target(cfg), rules=["no-filter-sized-reduce"])
    assert [f.rule for f in found] == ["no-filter-sized-reduce"]


def test_dense8_step_has_no_filter_sized_reduce():
    cfg = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 21,
                                  batch_size=8192)
    ep = _step_target(cfg)
    assert ep.extra["filter_elems"] == cfg.s
    assert lint_entry(ep, rules=["no-filter-sized-reduce"]) == []


# the counter-step bar (DESIGN §3.6): W well above every batch-event buffer
# (B·P decrement events, B·k set events) so the thresholds separate
COUNTER_CFG = dict(memory_bits=1 << 23, batch_size=1024, layout="planes")


def test_no_filter_sized_reduce_in_counter_step():
    """The SBF plane step's load is tracked from batch-event pre/post
    gathers — the compiled steady-state step must not reduce over any
    buffer as large as a plane (W words). The dense8 SBF branch's O(s)
    recount must NOT sneak back in through the plane path."""
    cfg = DedupConfig.for_variant("sbf", **COUNTER_CFG)
    ep = _step_target(cfg)
    assert ep.extra["separable"]       # B·P events below W by construction
    assert lint_entry(ep, rules=["no-filter-sized-reduce"]) == []


def test_counter_debug_exact_load_does_popcount_reduce():
    """Detector sanity: the escape hatch DOES reduce over the planes — via
    the raw helper this time, pinning what the rule counts as a reduce."""
    cfg = DedupConfig.for_variant("sbf", debug_exact_load=True, **COUNTER_CFG)
    hlo = Target(_step_target(cfg)).compiled_text()
    assert any(d >= cfg.s_words for d in reduce_operand_dims(hlo))


def test_counter_stream_donates_and_aliases_plane_state():
    """The SBF plane state (d, 1, W) is donated and aliased in place by the
    stream scan, same as the 1-bit filters (DESIGN §3.5/§3.6). The rule
    checks EVERY state leaf against the compiled input_output_alias table —
    strictly stronger than the old lowered-MLIR annotation grep."""
    cfg = DedupConfig.for_variant("sbf", **COUNTER_CFG)
    ep = stream_entry(cfg)
    assert any(".bits" in label for label, _, _ in ep.leaves())
    assert lint_entry(ep, rules=["state-donated-and-aliased"]) == []


def test_stream_donates_and_aliases_filter_state():
    """run_stream's jitted scan declares the state buffers donated (aliased
    to outputs) — the k·s-bit filter is updated in place, not copied."""
    cfg = DedupConfig.for_variant("rlbsbf", **CFG)
    ep = stream_entry(cfg)
    assert lint_entry(ep, rules=["state-donated-and-aliased"]) == []
    # the deliberately-undonated twin must trip the same rule
    broken = stream_entry(cfg, donate=False)
    assert "donated" not in broken.tags
    # (rule gates on the 'donated' tag — force-apply it to the broken twin)
    from repro.analysis.hlo_lint import HLO_RULES
    found = HLO_RULES["state-donated-and-aliased"].check(Target(broken))
    assert found and found[0].rule == "state-donated-and-aliased"


def test_run_stream_does_not_recompile():
    """Engine asymmetry regression (DESIGN.md §3.5): same-shape streams must
    reuse one compiled executable; get_engine shares engines per frozen cfg."""
    cfg = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 14,
                                  batch_size=256)
    d = get_engine(cfg)
    assert get_engine(DedupConfig.for_variant(
        "rlbsbf", memory_bits=1 << 14, batch_size=256)) is d
    keys = jnp.asarray(np.random.default_rng(0)
                       .integers(0, 1000, 1024).astype(np.uint32))
    base = d.stream_cache_size()
    st, _ = d.run_stream(d.init(), keys)
    after_one = d.stream_cache_size()
    st2, _ = d.run_stream(d.init(), keys)
    assert d.stream_cache_size() == after_one == base + 1
    # a different padded length is a new specialization — exactly one more
    _ = d.run_stream(d.init(), keys[:700])
    assert d.stream_cache_size() == base + 2


def test_process_does_not_donate_state():
    """process() must keep the argument state alive (interactive use): the
    same state can be processed twice."""
    cfg = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 14,
                                  batch_size=128)
    d = Dedup(cfg)
    st = d.init()
    keys = jnp.arange(128, dtype=jnp.uint32)
    _ = d.process(st, keys)
    _st2, res = d.process(st, keys)            # st still usable
    assert np.asarray(res.dup).shape == (128,)
