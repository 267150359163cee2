"""The share of a total that a part leaves: ``scale x (1 - numerator /
denominator)``, the ratio being ``stats_ratio``'s of the same window
deltas (rows launched that no entry filled, of rows launched)."""

from benchmark.readers import stats_ratio


def read(run, spec):
    ratio = stats_ratio.read(run, dict(spec, scale=1.0))
    if ratio is None:
        return None
    return spec.get("scale", 1.0) * (1.0 - ratio)
