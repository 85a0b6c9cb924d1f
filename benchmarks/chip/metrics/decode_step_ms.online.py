"""Mean decode step in the window, in ms:
read in the online cells (they report ``itl_p95_ms``)
by ``chipbench.readers.decode_step_ms``."""
from chipbench.readers import decode_step_ms as read  # noqa: F401
