"""Engine prefill time per thousand prompt tokens over the window
(``prefill_s`` / ``prefill_prompt_tokens``, engine counters)."""


def read(win):
    n = win.counters["prefill_prompt_tokens"]
    return win.counters["prefill_s"] / n * 1e6 if n else None
