"""The benchmark's own code: the yardstick later PRs may not change.

Nothing here is imported by the program under test.  From the program the
benchmark takes only the checker's public surface (``spawn_tpu`` / ``join`` /
counts / ``discoveries`` / ``checkpoint``), its flight-recorder records and
its kernel names in the profiler trace.
"""
