"""From a profiler trace to the benchmark's device and host numbers.

``extract(xplane_path)`` keeps what the reduction reads, as plain lists:
per device plane, the ``XLA Ops`` line's operations ``[label, mosaic,
start_ns, dur_ns]``; on the host, every event ``[thread, name, start_ns,
dur_ns]``.  Device and host events share one clock in the trace.

``Reduced`` works on that form (a small recorded one is checked in for
the tests) within the benchmark's own ``window`` span:

- busy time: the union of the device's operation intervals (operations
  nest on the line, so the union counts each instant once);
- Mosaic time: the summed durations of ``tpu_custom_call`` operations
  (Pallas kernels), which never nest in one another;
- self time of each operation (its duration less its children's), for
  the breakdown's top device operations;
- idle gaps: each idle instant of the device is put on the host event
  that the main thread was in at that instant, one level below the
  window span, or on ``"(no host event)"``.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, List, Tuple

#: The benchmark's span around the measured window.
WINDOW = "window"
NO_HOST = "(no host event)"

_OP = re.compile(r"^%?([^\s=]+) = ([^{( ]*)")


def op_label(name: str) -> Tuple[str, int]:
    """``(label, mosaic)`` of an HLO operation's trace name: the
    instruction name and its result type, and 1 for a Pallas kernel."""
    mosaic = int('custom_call_target="tpu_custom_call"' in name)
    m = _OP.match(name)
    label = f"{m.group(1)} {m.group(2)}" if m else name[:80]
    if mosaic:
        label += " tpu_custom_call"
    return label[:120], mosaic


def extract(path: str) -> dict:
    """The reduction's input, read from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = devices.setdefault(plane.name, [])
                    for e in line.events:
                        label, mosaic = op_label(e.name)
                        ops.append([label, mosaic, int(e.start_ns),
                                    int(e.duration_ns)])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    host.append([line.name, e.name, int(e.start_ns),
                                 int(e.duration_ns)])
    return {"devices": devices, "host": host}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


class Reduced:
    """Reductions of one extracted trace within its window span."""

    def __init__(self, data: dict, window: str = WINDOW):
        spans = [h for h in data["host"] if h[1] == window]
        if not spans:
            raise ValueError(f"no {window!r} span in the trace")
        thread, _, start, dur = max(spans, key=lambda h: h[3])
        self.t0, self.t1 = start, start + dur
        self.thread = thread
        self.devices = {name: [op for op in ops
                               if op[2] < self.t1 and op[2] + op[3] > self.t0]
                        for name, ops in sorted(data["devices"].items())}
        self.host = [h for h in data["host"]
                     if h[0] == thread and h[1] != window
                     and h[2] < self.t1 and h[2] + h[3] > self.t0]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _clip(self, a: int, b: int) -> Tuple[int, int]:
        return max(a, self.t0), min(b, self.t1)

    def busy_intervals(self, device: str) -> List[Tuple[int, int]]:
        return _union([self._clip(op[2], op[2] + op[3])
                       for op in self.devices[device]])

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices in the trace."""
        if not self.devices:
            return 0.0
        tot = sum(b - a for d in self.devices
                  for a, b in self.busy_intervals(d))
        return tot / len(self.devices) / 1e9

    def mosaic_s(self) -> float:
        """Pallas kernel seconds averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = 0
        for ops in self.devices.values():
            for op in ops:
                if op[1]:
                    a, b = self._clip(op[2], op[2] + op[3])
                    tot += b - a
        return tot / len(self.devices) / 1e9

    def device_ops(self, top: int = 10) -> List[list]:
        """``[[label, seconds], ...]``: the operations with the most self
        time (less their nested children), summed over devices."""
        self_t = self._self_times()
        return [[k, v / 1e9] for k, v in
                sorted(self_t.items(), key=lambda kv: -kv[1])[:top]]

    def _self_times(self) -> Dict[str, int]:
        out: Dict[str, int] = collections.Counter()
        for ops in self.devices.values():
            stack: List[list] = []          # [end, label, start, child]
            for label, _, start, dur in sorted(ops, key=lambda o: (o[2],
                                                                   -o[3])):
                a, b = self._clip(start, start + dur)
                while stack and stack[-1][0] <= a:
                    end, lab, s0, child = stack.pop()
                    out[lab] += (end - s0) - child
                if stack:
                    stack[-1][3] += b - a
                stack.append([b, label, a, 0])
            while stack:
                end, lab, s0, child = stack.pop()
                out[lab] += (end - s0) - child
        return out

    def _top_host(self) -> List[Tuple[int, int, str]]:
        """The main thread's events one level below the window span."""
        out: List[Tuple[int, int, str]] = []
        end = self.t0
        for _, name, start, dur in sorted(self.host,
                                          key=lambda h: (h[2], -h[3])):
            if start >= end:
                a, b = self._clip(start, start + dur)
                out.append((a, b, name))
                end = start + dur
        return out

    def idle_gaps(self, top: int = 10) -> List[list]:
        """``[[host activity, seconds], ...]``: the first device's idle
        time in the window, each instant put on what the host was
        doing."""
        device = next(iter(self.devices), None)
        busy = self.busy_intervals(device) if device else []
        idle, cur = [], self.t0
        for a, b in busy:
            if a > cur:
                idle.append((cur, a))
            cur = max(cur, b)
        if cur < self.t1:
            idle.append((cur, self.t1))
        host = self._top_host()
        starts = [h[0] for h in host]
        acc: Dict[str, int] = collections.Counter()
        for a, b in idle:
            covered = 0
            i = max(0, bisect.bisect_right(starts, a) - 1)
            while i < len(host) and host[i][0] < b:
                ha, hb, name = host[i]
                ov = min(b, hb) - max(a, ha)
                if ov > 0:
                    acc[name] += ov
                    covered += ov
                i += 1
            acc[NO_HOST] += (b - a) - covered
        if acc.get(NO_HOST) == 0:
            del acc[NO_HOST]
        return [[k, v / 1e9] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:top]]
