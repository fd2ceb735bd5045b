"""Mean over the traced decode launches (``engine.decode`` spans) of the
device time of the collective operations (all-reduce, all-gather,
reduce-scatter, all-to-all, collective-permute, their asynchronous halves,
and ``shard_map``'s ``psum``) inside the decode programs that start in
each, on chip 0."""
from bench.lib import engine_spans as E


def read(win):
    return E.decode_collective_ms(E.load(win))
