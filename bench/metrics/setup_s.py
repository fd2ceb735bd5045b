"""Process start to the window's opening: weights, compile-cache loads,
warm-up."""


def read(win):
    return win.setup_s
