"""Share of the traced window in which no operation ran on the device."""

from benchmark.harness import xplane


def read(run, spec):
    t = run.trace_summary
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return xplane.idle_share(t.busy_s, t.window_s)
