"""Engines under test, one file per kind, found by the ``engine`` of a
configuration file (``chipbench.kinds``). Each file has
``System(config, devices)`` with the filled starting state
(``initial_state``), the entry the window drives (``run_chunk``) and the
final filter's digests. These files and the serving loop are the
benchmark's only imports of the program."""
