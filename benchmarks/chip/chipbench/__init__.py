"""The chip benchmark's yardstick: traffic, seeded weights, the float32
reference, work counts and peaks, and the reduction of profiler traces.

Nothing in this package imports the program under test except
:mod:`chipbench.harness`, which drives it; the reference, the traffic
generator, the work counts and the trace reduction stand alone.
"""
