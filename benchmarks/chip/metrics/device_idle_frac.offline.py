"""Share of the traced span with no device op, in %:
read in the offline cell (it reports ``output_tok_s``)
by ``chipbench.readers.device_idle_frac``."""
from chipbench.readers import device_idle_frac as read  # noqa: F401
