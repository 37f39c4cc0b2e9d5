"""Sketch specifications — every variant as config over one template.

A ``SketchSpec`` (DESIGN.md §3.8) names the four ops that distinguish the
stream sketches of this repo — probe op, decision fn, event-delta op and the
randomness draw — plus the structural flags (family, plane count usage,
windowing) the step generators and the fused kernel generator need. The
generators — ``core.batched.make_templated_step`` (jnp) and
``kernels.fused_template.make_fused_step`` (Pallas) — consume the SAME spec,
tracing the same decision fn and the same word algebra on both backends, so
jnp/pallas bit-identity holds by construction for every registered sketch
and for any experimental spec passed in by hand.

Two families cover the paper's algorithms and the companion counting
sketches:

* ``bitset`` — k independent 1-bit rows, update R = (A & ~D) | I, randomness
  via ``BatchRandomness`` (rsbf, bsbf, bsbfsd, rlbsbf; arXiv:1212.3964 §4).
* ``counter`` — d bit-planes of one row of d-bit saturating cells, update
  subtract-then-(set|add) (sbf §5, swbf DESIGN §3.7, and the new cms/hh
  counting sketches §3.8).

Adding a sketch means registering a spec — no new kernel file, no new step
code. cms (count-min dedup with serve-path frequency estimates) and hh
(heavy-hitter flagging) are exactly that: pure config below.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax.numpy as jnp

from .batched import (CounterStepDeltas, count_event_deltas, draw_randomness,
                      draw_sbf_randomness, make_decision_fn,
                      ring_expire_planes, sbf_event_deltas)
from .config import DedupConfig


@dataclass(frozen=True)
class SketchSpec:
    """One sketch = one row of this table. Callables take ``cfg`` and close
    over it; structural flags drive operand layout in the kernel generator.

    make_decide(cfg) -> decide:
      bitset family: decide(vals, valid, seen, i_t, load, rnd)
                       -> (dup, insert, del_mask)     [``make_decision_fn``]
      counter family: decide(vals, valid, seen) -> dup — ``vals`` is (B, k)
        bool for probe="nonzero", (B, k) int32 cell values for probe="value";
        written value-dtype-agnostic (bool probe bits and int32 cell
        values read alike); both backends run it in XLA.
    draw(cfg, rng, b) -> (rng, rnd) or None when the sketch is deterministic
      (the rng then threads through the state untouched).
    make_events(cfg) -> events(state, pos, valid, rnd) -> CounterStepDeltas
      (counter family only; bitset events are the family-shared scatter).
    """
    name: str
    family: str                  # "bitset" | "counter"
    probe: str                   # "bits" | "nonzero" | "value"
    uses_seen: bool              # intra-batch first-occurrence join needed?
    windowed: bool               # consumes/pushes the WindowRing?
    combine: str                 # insert op: "ornot" | "add" | "set"
    has_sub: bool                # has a subtract (decay/expiry) operand?
    make_decide: Callable[[DedupConfig], Callable]
    draw: Optional[Callable]
    make_events: Optional[Callable[[DedupConfig], Callable]] = None
    thresholded: bool = False    # decide takes a ``t=`` count threshold the
                                 # fleet step overrides per tenant (§4.6)


# ---------------- counter-family decision fns ---------------------------- //
# Value-dtype-agnostic on purpose: ``vals != 0`` reads bool probe bits and
# int32 cell values identically, so one decide serves both probe ops.

def _decide_sbf(cfg: DedupConfig):
    def decide(vals, valid, seen):
        return jnp.all(vals != 0, axis=1) & valid
    return decide


def _decide_swbf(cfg: DedupConfig):
    def decide(vals, valid, seen):
        return (jnp.all(vals != 0, axis=1) | seen) & valid
    return decide


def _decide_cms(cfg: DedupConfig):
    t0 = cfg.count_threshold

    def decide(vals, valid, seen, t=t0):
        # count-min estimate >= threshold — at t == 1 this degenerates to
        # the counting-Bloom membership verdict (all k cells nonzero).
        # ``t`` defaults to the static config threshold; a fleet step passes
        # the per-tenant traced scalar instead (DESIGN §4.6)
        return ((jnp.min(vals, axis=1) >= t) | seen) & valid
    return decide


def _decide_hh(cfg: DedupConfig):
    t0 = cfg.count_threshold

    def decide(vals, valid, seen, t=t0):
        # heavy-hitter flag: long-run frequency only — an earlier equal key
        # in THIS batch says nothing about heaviness, so no ``seen`` join
        return (jnp.min(vals, axis=1) >= t) & valid
    return decide


# ---------------- counter-family event builders -------------------------- //

def _events_sbf(cfg: DedupConfig):
    def events(state, pos, valid, rnd) -> CounterStepDeltas:
        ev = sbf_event_deltas(cfg, pos, rnd, valid)
        return CounterStepDeltas(
            sub_planes=ev.count_planes, sub_events=ev.dec_sorted,
            sub_heads=ev.dec_head, add_planes=None, set_delta=ev.set_delta,
            ins_events=ev.set_sorted, ins_heads=ev.set_head,
            ring_payload=None)
    return events


def _events_swbf(cfg: DedupConfig):
    def events(state, pos, valid, rnd) -> CounterStepDeltas:
        ev = count_event_deltas(cfg, pos, valid, state.ring.events.shape[-1])
        exp_events, exp_heads, expire = ring_expire_planes(cfg, state.ring)
        return CounterStepDeltas(
            sub_planes=expire, sub_events=exp_events, sub_heads=exp_heads,
            add_planes=ev.count_planes, set_delta=None,
            ins_events=ev.ins_sorted, ins_heads=ev.ins_head,
            ring_payload=ev)
    return events


def _events_count(cfg: DedupConfig):
    def events(state, pos, valid, rnd) -> CounterStepDeltas:
        # no decay, no window: arrivals only ever increment (clamped at the
        # cell cap), which is what makes min-over-k an over-estimate
        ev = count_event_deltas(cfg, pos, valid, pos.shape[0] * cfg.k)
        return CounterStepDeltas(
            sub_planes=None, sub_events=None, sub_heads=None,
            add_planes=ev.count_planes, set_delta=None,
            ins_events=ev.ins_sorted, ins_heads=ev.ins_head,
            ring_payload=None)
    return events


# ---------------- the registry ------------------------------------------- //

def _bitset(name: str) -> SketchSpec:
    return SketchSpec(name=name, family="bitset", probe="bits",
                      uses_seen=True, windowed=False, combine="ornot",
                      has_sub=True, make_decide=make_decision_fn,
                      draw=draw_randomness)


SKETCHES = {
    "rsbf": _bitset("rsbf"),
    "bsbf": _bitset("bsbf"),
    "bsbfsd": _bitset("bsbfsd"),
    "rlbsbf": _bitset("rlbsbf"),
    "sbf": SketchSpec(name="sbf", family="counter", probe="nonzero",
                      uses_seen=False, windowed=False, combine="set",
                      has_sub=True, make_decide=_decide_sbf,
                      draw=draw_sbf_randomness, make_events=_events_sbf),
    "swbf": SketchSpec(name="swbf", family="counter", probe="nonzero",
                       uses_seen=True, windowed=True, combine="add",
                       has_sub=True, make_decide=_decide_swbf,
                       draw=None, make_events=_events_swbf),
    "cms": SketchSpec(name="cms", family="counter", probe="value",
                      uses_seen=True, windowed=False, combine="add",
                      has_sub=False, make_decide=_decide_cms,
                      draw=None, make_events=_events_count,
                      thresholded=True),
    "hh": SketchSpec(name="hh", family="counter", probe="value",
                     uses_seen=False, windowed=False, combine="add",
                     has_sub=False, make_decide=_decide_hh,
                     draw=None, make_events=_events_count,
                     thresholded=True),
}


def get_spec(variant: str) -> SketchSpec:
    """The variant's registered ``SketchSpec`` (DESIGN.md §3.8)."""
    try:
        return SKETCHES[variant]
    except KeyError:
        raise ValueError(
            f"no sketch spec registered for variant {variant!r} — "
            f"known: {sorted(SKETCHES)}") from None
