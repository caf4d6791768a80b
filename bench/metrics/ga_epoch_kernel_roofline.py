"""ga_epoch_kernel_roofline: percent of the HBM roofline that
`ga_epoch_kernel` (resident islands, ring migration in VMEM) reached in the
traced window: the least time its launches' bytes need at the chip's peak
HBM bandwidth (`bench/peaks.py`) over the time they took; bytes counted
from shapes (`bench/work.py`), the hoisted FFM constants included."""

from bench import work


def read(run):
    return work.roofline_share(run, "ga_epoch_kernel")
