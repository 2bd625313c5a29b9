"""The benchmark of tpu-tree-search: see run.py."""
