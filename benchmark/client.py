"""A load-client process: ``python3 benchmark/client.py``.

Started by the harness with ``JAX_PLATFORMS=cpu``. It reads one pickled
spec from stdin, runs the loop's ``client_main(spec, ctl)`` (which warms
up, reports ready, waits for the window's start time and drives the
window), and writes the pickled result to stdout. Only the harness that
started it writes to its stdin.
"""

from __future__ import annotations

import os
import pickle
import sys


class Control:
    """Ready/go handshake with the harness over the pipes."""

    def __init__(self, rx, tx) -> None:
        self.rx = rx
        self.tx = tx

    def send(self, obj) -> None:
        pickle.dump(obj, self.tx, protocol=pickle.HIGHEST_PROTOCOL)
        self.tx.flush()

    def ready(self, **info) -> float:
        """Report warm; block until the harness names the window's start
        (a ``time.monotonic`` instant) and return it."""
        self.send({"ready": True, **info})
        return float(pickle.load(self.rx)["t0"])


def main() -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.manifest import load_module

    rx, tx = sys.stdin.buffer, sys.stdout.buffer
    sys.stdout = sys.stderr  # stray prints must not corrupt the pipe
    spec = pickle.load(rx)
    loop = load_module(spec["root"], "loops", spec["loop"])
    ctl = Control(rx, tx)
    result = loop.client_main(spec, ctl)
    ctl.send({"result": result})
    return 0


if __name__ == "__main__":
    sys.exit(main())
