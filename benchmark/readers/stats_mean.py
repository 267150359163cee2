"""The mean of a ``get_stats`` histogram over the window: the block at the
metric file's ``path`` holds ``mean_us`` and ``count``, and ``mean x
count`` is an exact sum (the block's percentiles are log2 buckets, a
factor-2 resolution, and are not read).  Summed over the shards."""

from benchmark.readers.stats_ratio import at


def read(run, spec):
    if run.stats_before is None or run.stats_after is None:
        return None
    total, count = 0.0, 0.0
    for a, b in zip(run.stats_before["shards"], run.stats_after["shards"]):
        path = spec["path"]
        n_a = at(a, path + ".count") or 0
        n_b = at(b, path + ".count")
        if n_b is None:
            return None
        total += n_b * (at(b, path + ".mean_us") or 0.0) - n_a * (
            at(a, path + ".mean_us") or 0.0
        )
        count += n_b - n_a
    return total / count if count > 0 else None
