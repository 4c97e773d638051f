"""The benchmark's own tests run on the CPU; the benchmark and the
program go on the path."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402,F401  (puts bench/ and src/ on the path)
