"""The bytes the algorithm must move per engine step, from the
configuration and the batch shape alone.

Counted in 4-byte words, each word touched by a lane counted once, read or
written, whatever the implementation moves:

* every lane: its key in (4 B) and its verdict out (1 B);
* bitset family (k rows): k probed words read, k words written by the
  insert, and k words by the reset, the reset counted with the probability
  p_del = load/s the fill states;
* counter family (d planes): k probed cells read on each of d planes, k
  cells set on each plane, and the P-cell decrement run read and written
  on each plane (ceil(P·1/32) + 1 words: a run of P one-cell steps spans at
  most that many words of 32 cells).

The count does not depend on ``backend`` or layout, so a jnp step and a
fused kernel of the same configuration are held to the same bytes.
"""

from __future__ import annotations

import math

WORD = 4


def step_bytes(dedup: dict, fill: dict, lanes: int) -> float:
    """Bytes one step over ``lanes`` keys must move."""
    k = int(dedup["k"])
    per_lane = 4 + 1
    if dedup["variant"] == "sbf":
        d = int(dedup["sbf_max"]).bit_length()
        p_run = int(dedup["sbf_p"])
        run_words = math.ceil(p_run / 32) + 1
        per_lane += WORD * d * (k + k + 2 * run_words)
    else:
        p_del = float(fill["load_fraction"])
        per_lane += WORD * (k + k + k * p_del)
    return float(lanes) * per_lane
