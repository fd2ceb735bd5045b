"""99th percentile of the gaps between a request's output tokens."""
from bench.lib import readings as R
from bench.lib.stats import nearest_rank


def read(win):
    return nearest_rank(R.itl_ms(win), 0.99)
