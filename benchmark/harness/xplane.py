"""From a profiler trace (``*.xplane.pb``) to numbers: which intervals an
operation ran on each device, how long each kernel took, and the longest
gaps.  The reduction is the benchmark's, so every PR computes the same
number the same way.

What a v5e trace looks like (looked at by hand, PR 24): one plane per chip
named ``/device:TPU:<n>``; its line ``XLA Modules`` has one event per
launched program (``jit_<function>(<fingerprint>)``), its line ``XLA Ops``
one per HLO operation inside them.  Times are nanoseconds on one clock.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

DEVICE_PLANE = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def union_seconds(intervals) -> float:
    """Total length covered by (start, end) intervals, overlaps once."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list:
    """(start, length, index of the interval before it or -1) of every
    stretch of [lo, hi] that no interval covers, longest first."""
    out, edge, before = [], lo, -1
    for i, (s, e) in sorted(
        enumerate(intervals), key=lambda item: item[1]
    ):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > edge:
            out.append((edge, s - edge, before))
        if e > edge:
            edge, before = e, i
    if hi > edge:
        out.append((edge, hi - edge, before))
    return sorted(out, key=lambda g: -g[1])


def idle_share(busy_s: float, window_s: float) -> float:
    """Percent of the window in which no operation ran on the device."""
    return 100.0 * (1.0 - busy_s / window_s)


@dataclass
class DeviceEvents:
    """One chip's events, in seconds from the start of the trace."""

    name: str
    modules: list = field(default_factory=list)  # (name, start, end)
    ops: list = field(default_factory=list)


@dataclass
class TraceSummary:
    window_s: float
    devices: list  # DeviceEvents, one per chip that ran anything

    def _busy_events(self, dev: DeviceEvents) -> list:
        return dev.ops or dev.modules

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(
            union_seconds([(s, e) for _n, s, e in self._busy_events(d)])
            for d in self.devices
        ) / len(self.devices)

    def module_seconds(self, match) -> float:
        """Device seconds of the launched programs whose name holds one
        of ``match``, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(
            e - s
            for d in self.devices
            for n, s, e in d.modules
            if any(m in n for m in match)
        ) / len(self.devices)

    def module_count(self, match) -> int:
        return sum(
            1
            for d in self.devices
            for n, _s, _e in d.modules
            if any(m in n for m in match)
        )

    def breakdown(self, top: int = 10) -> dict:
        """The contract's ``breakdown``: the operations that took most
        device time, and the longest idle gaps.  A gap is named by the
        program that ended before it: what the HOST did in it cannot be
        said without spans inside the program (PERF.md, tracing list)."""
        per_op: dict = {}
        gap_rows: list = []
        for d in self.devices:
            for n, s, e in self._busy_events(d):
                n = n.split(" = ")[0]  # an op's event name is its whole HLO
                per_op[n] = per_op.get(n, 0.0) + (e - s)
            mods = d.modules or d.ops
            spans = [(s, e) for _n, s, e in mods]
            for _at, length, before in gaps(spans, 0.0, self.window_s)[:top]:
                after = mods[before][0] if before >= 0 else "trace_start"
                gap_rows.append([f"after:{after}"[:64], length])
        n = max(1, len(self.devices))
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        gap_rows.sort(key=lambda r: -r[1])
        return {
            "device_ops": [[k[:64], v / n] for k, v in ops],
            "idle_gaps": gap_rows[:top],
        }


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(
        glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
    )
    return found[-1] if found else None


def _summarise(profile, window_s: float | None) -> TraceSummary:
    t0, t1, raw = None, None, []
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        dev = DeviceEvents(plane.name)
        for line in plane.lines:
            if line.name not in (MODULE_LINE, OP_LINE):
                continue
            into = dev.modules if line.name == MODULE_LINE else dev.ops
            for ev in line.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                into.append((ev.name, s, e))
                t0 = s if t0 is None or s < t0 else t0
                t1 = e if t1 is None or e > t1 else t1
        if dev.modules or dev.ops:
            raw.append(dev)
    if t0 is None:
        return TraceSummary(window_s or 0.0, [])
    for dev in raw:
        dev.modules = [
            (n, (s - t0) / 1e9, (e - t0) / 1e9) for n, s, e in dev.modules
        ]
        dev.ops = [(n, (s - t0) / 1e9, (e - t0) / 1e9) for n, s, e in dev.ops]
    span = (t1 - t0) / 1e9
    return TraceSummary(max(window_s or 0.0, span), raw)


def read_trace(path: str, window_s: float | None = None) -> TraceSummary:
    """Reduce one ``.xplane.pb``.  ``window_s`` is the host's length of
    the traced window (start_trace to stop_trace); the summary keeps the
    longer of that and the span of the device's own events."""
    from jax.profiler import ProfileData

    return _summarise(ProfileData.from_file(path), window_s)


def read_text_trace(text: str, window_s: float | None = None) -> TraceSummary:
    """The same reduction over an XSpace text proto (the trimmed
    recording kept under benchmark/fixtures for the tests)."""
    from jax.profiler import ProfileData

    return _summarise(ProfileData.from_text_proto(text), window_s)


def _quoted(name: str) -> str:
    return '"' + name.replace("\\", "\\\\").replace('"', '\\"') + '"'


def trimmed_text_proto(path: str, per_line: int = 400) -> str:
    """An XSpace text proto of the device planes' module and op lines,
    the first ``per_line`` events of each: small enough to commit."""
    from jax.profiler import ProfileData

    out = []
    for pi, plane in enumerate(ProfileData.from_file(path).planes):
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        names: dict = {}
        lines = []
        for li, line in enumerate(plane.lines):
            if line.name not in (MODULE_LINE, OP_LINE):
                continue
            evs = []
            for ev in list(line.events)[:per_line]:
                mid = names.setdefault(ev.name, len(names) + 1)
                evs.append(
                    f"    events {{ metadata_id: {mid} "
                    f"offset_ps: {int(ev.start_ns * 1000)} "
                    f"duration_ps: {int(ev.duration_ns * 1000)} }}"
                )
            lines.append(
                f'  lines {{ id: {li + 1} name: "{line.name}"\n'
                + "\n".join(evs)
                + "\n  }"
            )
        meta = [
            f"  event_metadata {{ key: {mid} value {{ id: {mid} "
            f"name: {_quoted(name)} }} }}"
            for name, mid in names.items()
        ]
        out.append(
            f'planes {{ id: {pi + 1} name: "{plane.name}"\n'
            + "\n".join(lines + meta)
            + "\n}"
        )
    return "\n".join(out) + "\n"


def _dump(path: str) -> None:
    """``python -m benchmark.harness.xplane <file.xplane.pb>``: the
    planes, their lines and each line's heaviest event names — for
    looking at a trace by hand before writing a reader against it."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            total: dict = {}
            count = 0
            for ev in line.events:
                count += 1
                rec = total.setdefault(ev.name, [0, 0.0])
                rec[0] += 1
                rec[1] += ev.duration_ns / 1e9
            print(f"  LINE {line.name!r}: {count} events")
            top = sorted(total.items(), key=lambda kv: -kv[1][1])[:12]
            for name, (n, secs) in top:
                print(f"      {secs:10.6f}s x{n:<6d} {name[:100]}")
    summary = read_trace(path)
    print(
        f"SUMMARY window {summary.window_s:.4f}s busy {summary.busy_s:.4f}s "
        f"devices {[d.name for d in summary.devices]}"
    )


if __name__ == "__main__":
    import sys

    _dump(sys.argv[1])
