"""A kernel's share of its memory roofline: the bytes its launches had to
move (operands in + results out, from their shapes, recorded at each
launch while the trace ran) over the chip's published HBM bandwidth,
divided by the device time the trace shows for those launches."""

from benchmark.harness import peaks


def read(run, spec):
    t = run.trace_summary
    if t is None or not t.devices or not run.launches:
        return None
    kernel_s = t.module_seconds(spec["match"])
    if kernel_s <= 0 or t.module_count(spec["match"]) != len(run.launches):
        # A launch the trace missed, or one it saw that was not counted,
        # would put bytes and seconds out of step: no number then.
        return None
    moved = sum(b_in + b_out for _name, b_in, b_out in run.launches)
    least_s = moved / peaks.peak(run.device["kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / kernel_s
