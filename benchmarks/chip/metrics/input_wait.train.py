"""Device idle charged to ``repro.train.source``, the Trainer's wait for its
next batch (innermost open span at each idle instant), over the window; its
host time is logged beside it (bench/spans.py)."""
from bench.spans import input_wait as read  # noqa: F401
