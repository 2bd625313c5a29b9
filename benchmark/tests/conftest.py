"""The benchmark's tests run on the CPU at rehearsal sizes, with JAX's
persistent compilation cache off (each fault test compiles a broken
program that must not be served to the next)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
