"""Device seconds per epoch of the busiest chip's exchanges: the union of
its ``all-to-all`` ops in the traced window (each split op from start to
done, ``exchange.exchange_s``), over the epochs in the window."""
from benchmarks.chip.exchange import exchange_s


def read(run):
    per_dev = exchange_s(run.trace)
    if per_dev is None:
        return None
    return max(per_dev.values()) / run.stats["epochs"]
