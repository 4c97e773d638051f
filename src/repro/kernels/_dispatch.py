"""Shared backend dispatch for every kernel subpackage.

Two knobs, one convention, resolved here so the six kernel wrappers
can't drift:

  ``interpret`` — how a ``pl.pallas_call`` executes.  ``None`` (the
    default everywhere) auto-detects: compiled Mosaic on TPU, the Pallas
    interpreter everywhere else.  Callers that *measure or pin* the
    kernel body on CPU pass ``interpret=True`` explicitly.

  ``use_kernel`` — whether to run the Pallas kernel at all.  ``None``
    auto-detects: the kernel on TPU, the pure-jnp ref twin off-TPU.
    Ops that have a ref twin fast enough to serve as the off-TPU
    production path (decode_attention, topk_sample) take this second
    knob; the interpreter is *correct* everywhere but ~5x slower than
    plain XLA on CPU for small decode shapes, so it is the parity-test
    surface, never the serving path.
"""
from __future__ import annotations

import collections
import re
from typing import Optional

import jax

# a compiled Mosaic kernel's HLO line: the custom call's target and, in
# its op metadata, the jitted wrapper that holds the ``pallas_call``
_KERNEL_CALL = re.compile(
    r'custom_call_target="tpu_custom_call".*?'
    r'op_name="[^"]*?jit\((\w+)\)/pallas_call"')


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def auto_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve an ``interpret`` flag: compiled on TPU, interpreter else."""
    if interpret is None:
        return not on_tpu()
    return bool(interpret)


def auto_use_kernel(use_kernel: Optional[bool] = None) -> bool:
    """Resolve a ``use_kernel`` flag: Pallas on TPU, ref twin else."""
    if use_kernel is None:
        return on_tpu()
    return bool(use_kernel)


def compiled_kernels(compiled_text: str) -> collections.Counter:
    """{kernel wrapper name: number of compiled calls} in the text of a
    TPU executable (``jitted.lower(...).compile().as_text()``), e.g.
    ``{"topk_logits_tiles": 1}``.  Empty off TPU, where no kernel is
    compiled."""
    return collections.Counter(_KERNEL_CALL.findall(compiled_text))
