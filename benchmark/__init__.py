"""The benchmark of cluster_tools_tpu: harness, traffic, reference, metric readers.

Everything under this directory is the yardstick.  It takes from the program
only the system under test (the workflows that ``cluster_tools_tpu.cli run``
builds), its counters and spans, and the names of its device operations.
See README.md.
"""
