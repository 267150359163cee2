"""Device seconds, inside the traced window, of the launched programs
whose names hold one of the metric file's ``match`` strings."""


def read(run, spec):
    t = run.trace_summary
    if t is None or not t.devices:
        return None
    return t.module_seconds(spec["match"])
