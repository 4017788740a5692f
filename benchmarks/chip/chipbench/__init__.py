"""The chip benchmark's yardstick: traffic, the seed's key, what every
architecture's reference shares, work over a span and peaks, and the
reduction of profiler traces.  What is specific to one architecture
(its weights, reference, work counts, kernels and scopes) lives in
``archs/<arch>.py``, which a configuration file names.

Nothing in this package imports the program under test except
:mod:`chipbench.harness`, which drives it; the reference, the traffic
generator, the work counts and the trace reduction stand alone.
"""
