"""Device idle charged to any ``repro.store.*`` span (innermost open span at
each idle instant: fetch, write, checksum, manifest), over the window; the
device's wait for a batch's inputs after its forward returned is not the
store's and goes to ``input`` (bench/spans.py)."""
from bench.spans import idle_store as read  # noqa: F401
