"""90th percentile of time to first token over requests due in the window."""
from bench.lib import readings as R
from bench.lib.stats import nearest_rank


def read(win):
    return nearest_rank(R.ttft_ms(win), 0.90)
