"""Output tokens handed out in the window, over the window."""
from bench.lib import readings as R


def read(win):
    return R.tokens_in_window(win) / win.seconds
