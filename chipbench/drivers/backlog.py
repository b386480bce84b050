"""Backlog: every request is due at time 0 and there are more than the
window can serve, as in offline batch generation.  The window ends after
``seconds``; what was delivered by then counts."""

from __future__ import annotations


def drive(window, offered, seconds: float, mix: dict):
    window.open()
    for o in offered:
        window.submit(o)
    while window.now() < seconds and window.has_work():
        window.step()
    window.close()
