"""Production mesh builders.

Functions, not module-level constants: importing this module never
touches jax device state.  Device *count* is the runtime layer's job —
entry points call ``repro.runtime.env.bootstrap`` (host-platform
device-count override, e.g. 512 for the dry-run) before their first
jax import, then build meshes here over whatever that produced.
Worker-axis meshes for the GTC/BMUF strategies live in
``repro.runtime.cluster.worker_mesh`` (re-exported here): the widest
1D mesh the worker count divides onto.
"""
from __future__ import annotations

import jax

from repro.runtime.cluster import auto_mesh
from repro.runtime.cluster import worker_mesh  # noqa: F401  (re-export)


# the part the production meshes stand for, as JAX's Device.device_kind
# names it: the dry-run lowers on host devices shaped like a v5e pod and
# records this kind for the roofline's peak table
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e pod slice: 16x16 = 256 chips per pod; 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has, as a 1D data mesh (tests/examples)."""
    n = len(jax.devices())
    return auto_mesh((n, 1), ("data", "model"))
