"""Host time around each decode step in the window, in ms:
read in the offline cell (it reports ``output_tok_s``)
by ``chipbench.phase_readers.decode_host_ms``."""
from chipbench.phase_readers import decode_host_ms as read  # noqa: F401
