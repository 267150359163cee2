"""Start and stop ``jax.profiler`` around part of the window, in the
process that holds the chip, and reduce what it wrote."""

from __future__ import annotations

import os
import shutil
import time

from benchmark.harness import xplane

# The merge kernels whose launches the roofline counts (ops/bitonic.py).
MERGE_KERNELS = (
    "merge_runs_prefix32_packed_batch_kernel",
    "merge_runs_prefix64_packed_batch_kernel",
)


def profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    # Device events and JAX's own host spans; no Python call tracing,
    # which slows the host stages and makes the trace huge.
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


class LaunchSpy:
    """Records (kernel, operand bytes in, result bytes out) of every
    batch-merge launch while installed: the bytes a launch must move,
    from its shapes.  The pipeline looks the kernels up in ops.bitonic
    at each merge, so wrapping the module's names is enough."""

    def __init__(self) -> None:
        self.launches: list = []
        self._real: dict = {}

    def _wrap(self, name, kernel):
        def call(vals, counts, pack_bits):
            out = kernel(vals, counts, pack_bits)
            self.launches.append(
                (name, int(vals.nbytes) + int(counts.nbytes), int(out.nbytes))
            )
            return out

        return call

    def install(self) -> None:
        from dbeel_tpu.ops import bitonic

        for name in MERGE_KERNELS:
            self._real[name] = getattr(bitonic, name)
            setattr(bitonic, name, self._wrap(name, self._real[name]))

    def remove(self) -> None:
        from dbeel_tpu.ops import bitonic

        for name, kernel in self._real.items():
            setattr(bitonic, name, kernel)
        self._real = {}


class InProcessTracer:
    def __init__(self, run) -> None:
        self.run = run
        self.dir = os.path.join(run.work, "trace")
        self.on = False
        self.spy = LaunchSpy()
        self._t0 = 0.0

    def start(self) -> None:
        import jax

        self.spy.install()
        jax.profiler.start_trace(self.dir, profiler_options=profiler_options())
        self._t0 = time.perf_counter()
        self.on = True

    def stop(self) -> None:
        import jax

        window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        self.on = False
        self.spy.remove()
        self.run.launches = self.spy.launches
        finish(self.run, self.dir, window_s)


def finish(run, trace_dir: str, window_s: float) -> None:
    """Reduce the trace under ``trace_dir`` into ``run.trace_summary``,
    keep a copy where ``--keep-trace`` says, and remove the rest."""
    path = xplane.find_xplane(trace_dir)
    if path is not None:
        run.trace_summary = xplane.read_trace(path, window_s)
        keep = run.keep_trace
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(keep, "trace.xplane.pb"))
            with open(os.path.join(keep, "trimmed.textproto"), "w") as f:
                f.write(xplane.trimmed_text_proto(path))
    shutil.rmtree(trace_dir, ignore_errors=True)
