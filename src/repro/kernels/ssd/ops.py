"""Public wrapper for the SSD kernel."""
from __future__ import annotations

from repro.kernels.ssd import ref as R
from repro.kernels.ssd.kernel import ssd_chunked_kernel


def ssd(x, dt, A, B, C, *, chunk: int = 128, impl: str):
    """Dispatch on the required ``impl``: "pallas" (TPU) | "interpret" (CPU
    validation) | "xla" (oracle)."""
    if impl == "xla":
        y, st = R.ssd_ref(x, dt, A, B, C)
        return y.astype(x.dtype), st
    return ssd_chunked_kernel(x, dt, A, B, C, chunk=chunk,
                              interpret=impl == "interpret")
