"""Model FLOPs of the traced decode launches over their device time at
the chip's peak bf16 rate, in %."""
from bench.lib import readings as R


def read(win):
    return R.decode_mfu(win)
