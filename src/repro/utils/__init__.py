"""Shared helpers, imported by submodule (``repro.utils.trees``,
``repro.utils.tracing``, ...).  This package imports none of them itself:
``trees`` imports jax, and ``tracing`` is opened by modules that must stay
numpy-only at import (``repro.store``, ``repro.pipeline.generate``)."""
