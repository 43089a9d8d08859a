"""Bounded waits on child processes. ``pytest-timeout`` is not installed, so
every test that waits on a child, a socket or a thread carries a bound of its
own: a child that hangs must fail its test, not stall the whole tier-1 run."""

from __future__ import annotations

import threading


def readline_bounded(proc, timeout_s: float = 180.0) -> str:
    """One line of ``proc``'s stdout. A child that prints nothing within
    ``timeout_s`` is killed and the test fails; EOF returns ``""`` as
    ``readline`` does."""
    box = []
    t = threading.Thread(target=lambda: box.append(proc.stdout.readline()),
                         daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        proc.kill()
        raise AssertionError(
            f"child {proc.args[:2]} printed nothing within {timeout_s}s")
    return box[0]
