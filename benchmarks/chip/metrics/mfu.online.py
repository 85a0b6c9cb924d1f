"""Model FLOPs of the traced span's work over the bf16 peak, in %:
read in the online cells (they report ``itl_p95_ms``)
by ``chipbench.readers.mfu``."""
from chipbench.readers import mfu as read  # noqa: F401
