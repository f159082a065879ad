from repro.kernels.bsc.ops import bsc_bitplane_copies  # noqa: F401
