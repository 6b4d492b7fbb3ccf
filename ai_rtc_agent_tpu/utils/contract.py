"""Contract-line plumbing shared by the measurement CLIs.

The scripts/*_bench.py and check scripts print one JSON line on every exit
path from a finally block.  The finally-block pattern handles exceptions;
this helper covers the remaining hole: a SIGTERM from timeout(1) would
otherwise kill the process without running the finally block, losing the
error detail of the attempt.
"""

from __future__ import annotations

import signal


def sigterm_to_exception(source: str = "driver timeout") -> None:
    """Install a SIGTERM handler that raises TimeoutError.

    The exception unwinds into the caller's ``except/finally`` so the
    contract line is still emitted.  Note the known limit: if the main
    thread is blocked inside a C call (e.g. a wedged device dispatch),
    the Python-level handler cannot run until that call returns — the
    caller's ``timeout -k`` escalates to SIGKILL for exactly that case.
    """

    def _raise(signum, frame):
        raise TimeoutError(f"SIGTERM ({source})")

    signal.signal(signal.SIGTERM, _raise)
