"""A number the deployment or the generator observed in the window,
under the name the metric's file gives (``fact``)."""


def read(run, spec):
    return run.facts.get(spec["fact"])
