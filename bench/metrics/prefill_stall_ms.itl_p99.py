"""Mean over the traced ticks that ran a prefill of the time the tick spent
in ``engine.prefill`` spans: how long its decode rows waited."""
from bench.lib import engine_spans as E


def read(win):
    return E.prefill_stall_ms(E.load(win))
