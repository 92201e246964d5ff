"""Reduction from a profiler trace (``.xplane.pb``) to device busy and
idle time, per-operation time and attribution of idle gaps."""
