"""The chip benchmark: served-path cells for the JAX engine on a TPU.

Everything that decides a number lives here (traffic generation, the
reduction from traces, spans and counters to metrics, the table of peaks,
the plain reference and the comparison that decides ``correct``). From
the program the benchmark takes only the system under test and its
spans, counters and kernel names. See README.md in this directory.
"""
