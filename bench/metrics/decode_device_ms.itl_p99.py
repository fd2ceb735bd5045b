"""Mean over the traced decode launches (``engine.decode`` spans) of the
device time of the decode programs that start inside each."""
from bench.lib import engine_spans as E


def read(win):
    return E.decode_device_ms(E.load(win))
