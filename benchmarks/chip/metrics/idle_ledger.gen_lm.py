"""Device idle charged to the generation work ledger's ``repro.gen.ledger``
spans (innermost open span at each idle instant), over the window
(bench/spans.py)."""
from bench.spans import idle_ledger as read  # noqa: F401
