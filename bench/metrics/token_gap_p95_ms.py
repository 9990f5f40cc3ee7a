"""95th percentile of the gaps between consecutive tokens of a
sequence, as the host fetched them, over the whole window."""
import statistics


def read(drv):
    gaps = drv.gaps_ms()
    return statistics.quantiles(gaps, n=20)[18] if len(gaps) >= 20 else None
