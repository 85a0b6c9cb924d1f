"""Model FLOPs of the traced span's work over the bf16 peak, in %:
read in the offline cell (it reports ``output_tok_s``)
by ``chipbench.readers.mfu``."""
from chipbench.readers import mfu as read  # noqa: F401
