from repro.kernels.paged_qattn.ops import (  # noqa: F401
    attend_paged, kernel_supported, policy_supported)
