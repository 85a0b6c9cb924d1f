"""Share of the traced span with no device op, in %:
read in the online cells (they report ``itl_p95_ms``)
by ``chipbench.readers.device_idle_frac``."""
from chipbench.readers import device_idle_frac as read  # noqa: F401
