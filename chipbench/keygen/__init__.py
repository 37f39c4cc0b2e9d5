"""Key generators, one file per kind, found by the ``kind`` of a traffic
file's ``keys`` block (``chipbench.kinds``). Each file has
``make(params, seed, chunk)``, which returns an object whose ``next`` gives
the next keys; the same seed gives the same keys."""

KEY_STREAM = 11     # the seed stream keys are drawn from
