"""Reduction of a JAX profiler trace to device metrics.

The benchmark traces a steady sub-window of a run with ``jax.profiler`` and
marks it with a ``bench_window`` host annotation; the program's host spans
(``plan``, ``stage``, ``dispatch``, ``complete``) and the client's own
(``enqueue``, ``client_wait``) are host annotations on the same clock as the
device's operations. From the ``.xplane.pb`` file this module takes:

* the device's operations: the events of the ``XLA Ops`` line of each
  ``/device:TPU:<i>`` plane, clipped to the window, named by their HLO
  instruction name;
* busy time: the length of the union of those intervals, averaged over the
  chips; idle share = 1 - busy / window;
* time by operation name, and the operations that took most time;
* the idle gaps between busy intervals, each split among the host spans
  that were open during it (time under no span is ``no host span``).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench_window"
HOST_SPANS = ("plan", "stage", "dispatch", "complete", "enqueue",
              "client_wait")
NO_SPAN = "no host span"

Interval = Tuple[str, int, int]  # (name, start_ns, end_ns)

_HLO_NAME = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?(?:\s*=|$)")


def short_name(op: str) -> str:
    """An op's HLO instruction name without its numeric suffix: the TPU
    trace names an op by its whole instruction text
    (``%_sbmm_raw_jit.10 = f32[...] custom-call(...)`` -> ``_sbmm_raw_jit``).
    """
    m = _HLO_NAME.match(op)
    return m.group(1) if m else op


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]                  # ns, host clock
    chips: List[List[Interval]]              # device ops per chip, clipped
    host: List[Interval]                     # host spans of HOST_SPANS

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _profile_data(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def load(path: str) -> Trace:
    """Read the window, the device ops and the host spans of one trace."""
    pd = _profile_data(path)
    window = None
    host: List[Interval] = []
    devices: List[List[Interval]] = []
    for plane in pd.planes:
        name = plane.name
        if DEVICE_PLANE.match(name):
            ops: List[Interval] = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((short_name(e.name), int(e.start_ns),
                                int(e.start_ns + e.duration_ns))
                               for e in line.events)
            devices.append(ops)
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                    elif e.name in HOST_SPANS:
                        host.append((e.name, int(e.start_ns),
                                     int(e.start_ns + e.duration_ns)))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    lo, hi = window
    chips = [sorted((n, max(a, lo), min(b, hi)) for n, a, b in ops
                    if b > lo and a < hi) for ops in devices]
    host = sorted(((n, max(a, lo), min(b, hi)) for n, a, b in host
                   if b > lo and a < hi), key=lambda s: s[1])
    return Trace(window=window, chips=chips, host=host)


def busy_intervals(ops: Sequence[Interval]) -> List[Tuple[int, int]]:
    """Union of the op intervals, as sorted disjoint (start, end) pairs."""
    out: List[List[int]] = []
    for _, a, b in sorted(ops, key=lambda o: o[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    if not trace.chips:
        return 0.0
    tot = sum(sum(b - a for a, b in busy_intervals(ops))
              for ops in trace.chips)
    return tot * 1e-9 / len(trace.chips)


def idle_share(trace: Trace) -> Optional[float]:
    if not trace.chips or trace.window_s <= 0:
        return None
    return 1.0 - busy_s(trace) / trace.window_s


def op_seconds(trace: Trace) -> Dict[str, float]:
    """Device seconds by operation name, summed over the chips."""
    out: Dict[str, float] = defaultdict(float)
    for ops in trace.chips:
        for name, a, b in ops:
            out[name] += (b - a) * 1e-9
    return dict(out)


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    secs = op_seconds(trace)
    return [[k, v] for k, v in sorted(secs.items(), key=lambda kv: -kv[1])
            [:n]]


def matching_seconds(trace: Trace, pattern: str) -> Tuple[float, int]:
    """(device seconds, event count) of the ops whose name matches
    ``pattern`` (a regular expression searched in the name)."""
    rx = re.compile(pattern)
    secs, count = 0.0, 0
    for ops in trace.chips:
        for name, a, b in ops:
            if rx.search(name):
                secs += (b - a) * 1e-9
                count += 1
    return secs, count


def idle_gaps(trace: Trace) -> List[Tuple[int, int]]:
    """Idle intervals of the first chip inside the window."""
    lo, hi = trace.window
    if not trace.chips:
        return [(lo, hi)]
    gaps, t = [], lo
    for a, b in busy_intervals(trace.chips[0]):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def idle_by_host_span(trace: Trace, n: int = 10) -> List[List]:
    """Idle seconds of the device, split among the host spans open during
    each gap; largest first."""
    starts = [s[1] for s in trace.host]
    longest = max((b - a for _, a, b in trace.host), default=0)
    out: Dict[str, float] = defaultdict(float)
    # host spans nest (dispatch inside a tick, stage inside plan's caller):
    # credit each instant of a gap to the innermost, i.e. latest-started,
    # open span
    for g0, g1 in idle_gaps(trace):
        cover: List[Tuple[int, int, str]] = []
        i = bisect.bisect_right(starts, g1)
        j = bisect.bisect_left(starts, g0 - longest)
        for name, a, b in trace.host[j:i]:
            if b > g0:
                cover.append((max(a, g0), min(b, g1), name))
        marks = sorted({g0, g1, *[c[0] for c in cover],
                        *[c[1] for c in cover]})
        for x0, x1 in zip(marks, marks[1:]):
            open_ = [c for c in cover if c[0] <= x0 and c[1] >= x1]
            name = max(open_, key=lambda c: c[0])[2] if open_ else NO_SPAN
            out[name] += (x1 - x0) * 1e-9
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])
            [:n]]
