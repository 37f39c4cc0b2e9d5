"""Arrival loops, one file per kind, found by the ``kind`` of a traffic
file's ``arrival`` block (``chipbench.kinds``). Each file has
``run(cell, devices, tracer)``: it builds the system, warms up the cell's
shapes, measures its window and compares what the window produced with
the plain reference."""
