"""The benchmark of spark-rapids-tpu: harness, traffic, references, trace
reduction and peaks. The command is ``perfbench/run.py``; everything that
belongs to one configuration, cell, query or per-layer metric is a file of
its own that the harness finds by name."""
