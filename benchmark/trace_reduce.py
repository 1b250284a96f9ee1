"""From a profiler trace to device time per program, busy time, idle
share and what the host did in the idle gaps.

`events_from_profile` reads a `jax.profiler.ProfileData` into a plain
dict: per TPU, the program executions (the "XLA Modules" line), and
the host's annotations. A trace without them is an error: device time
is never read from the host. Only a trace recorded on the CPU, read
with `cpu=True`, takes the operations on the XLA runtime's host threads
as its one device. `reduce` turns that dict and a window into numbers.
Times in the dict are nanoseconds on the trace's own clock.

Busy time is the union of the intervals in which a program ran on the
device, averaged over the devices that ran programs; idle is the rest
of the window. The per-operation line is not read: on the chip it holds
millions of events for a few seconds of these looping programs, and
its busy time equals the programs' to within a few percent. A program
is named by its jit name with the trailing "(id)" dropped, so the
staged programs read as `jit__stage_hash`, `jit__stage_prep` and so on.
"""

from __future__ import annotations

import re
import statistics
from dataclasses import dataclass, field

_ID_SUFFIX = re.compile(r"\(\d+\)$")
HOST_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU"
# the event a device plane carries once its trace buffer overflowed
DROPPED = "Trace Buffers Dropped"


class TraceError(ValueError):
    pass


def program_name(name: str) -> str:
    return _ID_SUFFIX.sub("", name)


def events_from_profile(pd, cpu: bool = False) -> dict:
    """Program executions per TPU and the host's annotations. Raises
    TraceError when no TPU plane has program executions, unless `cpu`,
    where the host's XLA operations stand for the device."""
    devices: dict = {}
    host: list = []
    cpu_ops: list = []
    dropped: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev = devices.setdefault(plane.name, {"modules": [], "ops": []})
                    dev["modules"] += [
                        [e.name, e.start_ns, e.duration_ns] for e in line.events
                    ]
                elif line.name == "XLA TraceMe":
                    dropped += [e.start_ns for e in line.events if e.name == DROPPED]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                cpu_line = line.name.startswith("tf_XLA")
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, e.start_ns, e.duration_ns])
                    elif cpu_line and e.duration_ns > 0 and "::" not in e.name:
                        cpu_ops.append([e.name, e.start_ns, e.duration_ns])
    if cpu and not devices and cpu_ops:
        devices["/host:CPU"] = {"modules": [], "ops": cpu_ops}
    if not any(d["modules"] or d["ops"] for d in devices.values()):
        raise TraceError(
            "the trace has no program executions on a TPU plane"
            + ("" if cpu else " (a profiler that missed the device?)")
        )
    out = {"devices": devices, "host": host}
    if dropped:
        out["dropped_ns"] = min(dropped)
    return out


def union(intervals) -> list:
    """Merged, sorted [start, end] intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


@dataclass
class Summary:
    window_s: float
    busy_s: float
    # name -> [seconds, launches] of the launches inside the window
    programs: dict = field(default_factory=dict)
    # name -> [[start ms after the window opens, device ms], ...] of the
    # launches inside the window, on every device
    launches: dict = field(default_factory=dict)
    idle_by_host: list = field(default_factory=list)  # [[label, seconds], ...]

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def ms_per_launch(self, fragment: str):
        """Median device milliseconds of one launch of the matching
        programs, so that a stray short launch of the same program does
        not pull it down; every launch is on the `launches` line."""
        ms = [d for k, v in self.launches.items() if fragment in k for _, d in v]
        return statistics.median(ms) if ms else None

    def top_programs(self, n: int = 10) -> list:
        ranked = sorted(self.programs.items(), key=lambda kv: -kv[1][0])
        return [[k, v[0]] for k, v in ranked[:n]]


def _label(gap, host) -> str:
    """The innermost host interval that holds the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    best, best_len = "untraced", None
    for name, s, e in host:
        if s <= mid < e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def reduce(events: dict, window, host_spans=()) -> Summary:
    """Numbers for `window` = (start_ns, end_ns). `host_spans` adds
    [name, start_ns, end_ns] intervals of the program's own spans, on
    the trace's clock, to the annotations that label idle gaps."""
    lo, hi = window
    if not any(d["modules"] or d["ops"] for d in events["devices"].values()):
        raise TraceError("no device executions to reduce")
    # past an overflow of the device's trace buffer the trace is incomplete
    hi = min(hi, events.get("dropped_ns", hi))
    if hi <= lo:
        raise ValueError("empty trace window")
    busy_per_device = []
    gaps = []
    programs: dict = {}
    launches: dict = {}
    for d, (name, dev) in enumerate(sorted(events["devices"].items())):
        src = dev["modules"] or dev["ops"]
        busy = union(_clip([[s, s + n] for _, s, n in src], lo, hi))
        busy_per_device.append(sum(e - s for s, e in busy))
        if d == 0:
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            gaps = [
                [edges[i], edges[i + 1]]
                for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]
            ]
        # a launch cut by either edge of the window would read short
        for pname, s, n in dev["modules"]:
            if lo <= s and s + n <= hi:
                acc = programs.setdefault(program_name(pname), [0.0, 0])
                acc[0] += n / 1e9
                acc[1] += 1
                launches.setdefault(program_name(pname), []).append(
                    [(s - lo) / 1e6, n / 1e6]
                )
    host = [[n, s, s + d] for n, s, d in events["host"]]
    host += [list(h) for h in host_spans]
    idle: dict = {}
    for g in gaps:
        label = _label(g, host)
        idle[label] = idle.get(label, 0.0) + (g[1] - g[0]) / 1e9
    ranked = sorted(idle.items(), key=lambda kv: -kv[1])
    busy_s = sum(busy_per_device) / len(busy_per_device) / 1e9
    if busy_s <= 0:
        raise TraceError("no device execution inside the traced window")
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_s,
        programs=programs,
        launches=launches,
        idle_by_host=[[k, v] for k, v in ranked[:10]],
    )


def window_of(events: dict, name: str = HOST_PREFIX + "window"):
    """(start_ns, end_ns) of the host annotation that marks the window."""
    for n, s, d in events["host"]:
        if n == name:
            return s, s + d
    raise ValueError(f"no {name!r} annotation in the trace")
