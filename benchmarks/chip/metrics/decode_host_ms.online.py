"""Host time around each decode step in the window, in ms:
read in the online cells (they report ``itl_p95_ms``)
by ``chipbench.phase_readers.decode_host_ms``."""
from chipbench.phase_readers import decode_host_ms as read  # noqa: F401
