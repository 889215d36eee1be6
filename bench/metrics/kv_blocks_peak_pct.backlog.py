"""Peak blocks in use of the paged arena (``BlockPool`` peak), as a share
of its usable blocks."""

from bench import readers


def read(run):
    return readers.kv_blocks_peak_pct(run)
