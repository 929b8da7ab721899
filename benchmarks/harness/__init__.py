"""The benchmark's own code: traffic, data, wire client, reducers, peaks.

Nothing here imports `tidb_tpu` except `serve.py`, which starts the system
under test, and `spans.py`, which takes its finished span trees.
"""
