#!/usr/bin/env python3
"""Runs one node exactly as ``python -m dbeel_tpu.server.run`` does (the
same ``main(argv)``, on the main thread, with the same flags) and, because
only the process that holds the chip can trace it or ask its memory,
answers a few commands from the benchmark's runner beside it.

Commands arrive as lines on standard input; each answer is one line on
standard output that starts with ``@ctl `` followed by JSON.  The node's
own log goes to standard error.  The wrapper runs the same way traced and
untraced, so the two runs differ by the profiler alone.

    trace_start <dir>   start jax.profiler into <dir>
    trace_stop          stop it; answers the seconds it ran
    compiles            backend compilations so far: [monotonic time, seconds]
    memory              peak_bytes_in_use of the fullest device
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


class Control(threading.Thread):
    def __init__(self) -> None:
        super().__init__(daemon=True, name="bench-control")
        from benchmark.harness.compiles import Compiles

        self.compiles = Compiles()
        self._trace_t0 = None

    def answer(self, words) -> dict:
        import jax

        if words[0] == "trace_start":
            from benchmark.harness.tracing import profiler_options

            jax.profiler.start_trace(
                words[1], profiler_options=profiler_options()
            )
            self._trace_t0 = time.monotonic()
            return {"ok": True}
        if words[0] == "trace_stop":
            window_s = time.monotonic() - self._trace_t0
            jax.profiler.stop_trace()
            return {"ok": True, "window_s": window_s}
        if words[0] == "compiles":
            seen = self.compiles
            return {"ok": True, "compiles": list(seen.events),
                    "cache": {"hits": seen.hits, "misses": seen.misses}}
        if words[0] == "memory":
            peaks = [
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.devices()
            ]
            return {"ok": True, "memory_peak_bytes": int(max(peaks))}
        return {"ok": False, "error": f"unknown command {words[0]!r}"}

    def run(self) -> None:
        for line in sys.stdin:
            words = line.split()
            if not words:
                continue
            try:
                reply = self.answer(words)
            except Exception as e:  # the runner reports it and fails
                reply = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            reply["cmd"] = words[0]
            sys.stdout.write("@ctl " + json.dumps(reply) + "\n")
            sys.stdout.flush()


def main(argv) -> None:
    from dbeel_tpu.server import run as server_run

    Control().start()
    server_run.main(argv)


if __name__ == "__main__":
    main(sys.argv[1:])
