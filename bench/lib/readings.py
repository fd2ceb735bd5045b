"""Arithmetic the metric readers share: latencies of the window's requests,
FLOPs of its decode launches, and shares of the device's time and peaks.

Every function takes the ``driver.Window`` of one run and returns a number
or ``None`` when the run holds nothing to read.
"""
from __future__ import annotations

from typing import List, Optional

from bench.lib import flops as F
from bench.lib.stats import median


def ttft_ms(win) -> List[float]:
    """First-token time minus due time of every request due in the window;
    one that never got a token counts until the run gave up on it."""
    return [((r.token_t[0] if r.token_t else win.t_end) - r.due_t) * 1e3
            for r in win.recs if r.in_window]


def queue_wait_ms(win) -> List[float]:
    """Due time to the start of the tick that admitted the request."""
    return [((r.admit_t if r.admit_t is not None else win.t_end) - r.due_t) * 1e3
            for r in win.recs if r.in_window]


def itl_ms(win) -> List[float]:
    """Every gap between consecutive tokens of a request whose later token
    came out inside the window."""
    out = []
    for r in win.recs:
        t = r.token_t
        out.extend((b - a) * 1e3 for a, b in zip(t, t[1:]) if win.in_window(b))
    return out


def tokens_in_window(win) -> int:
    return sum(win.in_window(t) for r in win.recs for t in r.token_t)


def decode_step_ms(win) -> Optional[float]:
    return median(win.decode_step_ms)


def _traced_launches(win):
    tr = win.trace
    return [l for t in win.ticks if tr.t0 <= t.t0 < tr.t1 for l in t.launches]


def decode_mfu(win) -> Optional[float]:
    """Model FLOPs of the decode launches whose ticks began in the traced
    window, over the device time of the decode programs there at the chip's
    peak bf16 rate, in %."""
    tr = win.trace
    if tr is None or not tr.decode_s:
        return None
    c = win.config
    fl = sum(F.decode_token_flops(c, w, l.depth, ctx)
             for l in _traced_launches(win) for w, ctx in l.rows)
    return 100.0 * fl / (tr.decode_s * win.peaks["bf16_flops_per_s"])


def device_idle(win) -> Optional[float]:
    tr = win.trace
    if tr is None or not tr.window_s:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
