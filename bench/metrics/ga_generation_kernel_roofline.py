"""ga_generation_kernel_roofline: percent of the HBM roofline that
`ga_generation_kernel` reached in the traced window: the least time its
launches' bytes need at the chip's peak HBM bandwidth (`bench/peaks.py`)
over the time they took; bytes counted from shapes (`bench/work.py`)."""

from bench import work


def read(run):
    return work.roofline_share(run, "ga_generation_kernel")
