from repro.kernels.moe_gmm.ops import moe_gmm
from repro.kernels.moe_gmm.ref import moe_gmm_ref

__all__ = ["moe_gmm", "moe_gmm_ref"]
