"""Median of the engine's decode-step histogram over the window (host
clock around a launch that ends in ``block_until_ready``)."""
from bench.lib import readings as R


def read(win):
    return R.decode_step_ms(win)
