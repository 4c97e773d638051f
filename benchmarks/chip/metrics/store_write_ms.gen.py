"""Median over the window's shards of ``repro.store.append_shard`` less its
``repro.store.fetch`` child: the store's host time per shard once its data
is on the host (bench/spans.py)."""
from bench.spans import store_write_ms as read  # noqa: F401
