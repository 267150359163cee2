"""A ratio of window deltas of ``get_stats`` counters.

The metric's file gives ``numerator`` and ``denominator``: lists of dotted
paths.  A path that starts with ``node.`` is read once (the block is the
process's, the same from every shard); any other is summed over the
shards.  ``fact:<name>`` takes one of the run's facts instead.  ``scale``
multiplies the result (100 for a share in percent)."""


def at(block, dotted):
    for part in dotted.split("."):
        if not isinstance(block, dict) or part not in block:
            return None
        block = block[part]
    return block if isinstance(block, (int, float)) else None


def _value(snapshot, path):
    if path.startswith("node."):
        return at(snapshot["node"], path[5:])
    values = [at(shard, path) for shard in snapshot["shards"]]
    return None if not values or None in values else sum(values)


def _delta_sum(run, paths):
    total = 0.0
    for path in paths:
        if path.startswith("fact:"):
            got = run.facts.get(path[5:])
        else:
            a = _value(run.stats_before, path)
            b = _value(run.stats_after, path)
            got = None if a is None or b is None else b - a
        if got is None:
            return None
        total += got
    return total


def read(run, spec):
    if run.stats_before is None or run.stats_after is None:
        return None
    num = _delta_sum(run, spec["numerator"])
    den = _delta_sum(run, spec["denominator"])
    if num is None or not den:
        return None
    return spec.get("scale", 1.0) * num / den
