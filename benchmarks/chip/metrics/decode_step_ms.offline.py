"""Mean decode step in the window, in ms:
read in the offline cell (it reports ``output_tok_s``)
by ``chipbench.readers.decode_step_ms``."""
from chipbench.readers import decode_step_ms as read  # noqa: F401
