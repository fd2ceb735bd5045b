"""Share of the traced window in which no operation ran on the device."""
from bench.lib import readings as R


def read(win):
    return R.device_idle(win)
