"""90th percentile of due time to the tick that admitted the request."""
from bench.lib import readings as R
from bench.lib.stats import nearest_rank


def read(win):
    return nearest_rank(R.queue_wait_ms(win), 0.90)
