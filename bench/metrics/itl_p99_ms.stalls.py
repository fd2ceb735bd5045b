"""99th percentile of the gaps between a request's output tokens, where a
few ticks that stall for about 0.1 s each decide it (``PERF.md``)."""
from bench.lib import readings as R
from bench.lib.stats import nearest_rank


def read(win):
    return nearest_rank(R.itl_ms(win), 0.99)
