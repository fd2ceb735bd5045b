"""Mean over the traced decode launches (``engine.decode`` spans) of the
span's time minus its decode programs' device time: the host's part of a
launch."""
from bench.lib import engine_spans as E


def read(win):
    return E.decode_host_ms(E.load(win))
