"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the
per-layer readers and the result line use: the device operations inside
the benchmark's traced window, the device's busy seconds (the union of
its operation intervals, averaged over the chips), and the longest idle
gaps, each named by what the host was doing in it.
"""
from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_SPAN = "chipbench.window"
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def short_name(op: str) -> str:
    """An XLA op event is named by its HLO text; its short name is the
    instruction's name without the numeric suffix ("%fusion.12 = ..."
    -> "%fusion")."""
    return re.sub(r"\.\d+$", "", op.split(" = ", 1)[0])


def _shapes(text: str) -> list:
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text)]


def custom_call(op: str):
    """(result shapes, operand shapes) of a Pallas kernel's op text, or
    None for any other op. Shapes are (dtype, dims) as the HLO prints
    them, padding included."""
    if 'custom_call_target="tpu_custom_call"' not in op or " = " not in op:
        return None
    head, rest = op.split(" = ", 1)
    res, _, args = rest.partition(" custom-call(")
    args = args.split("), custom_call_target=", 1)[0]
    return _shapes(res), _shapes(args)


@dataclass
class Trace:
    window: tuple                       # (start_ns, end_ns) on the host
    ops: dict = field(default_factory=dict)    # device -> [(name, s, e)]
    host: list = field(default_factory=list)   # [(name, s, e)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def device_ops(self):
        """Every device operation inside the window, over all chips:
        (device, name, start_ns, end_ns)."""
        w0, w1 = self.window
        return [(d, n, max(s, w0), min(e, w1))
                for d, evs in self.ops.items() for n, s, e in evs
                if e > w0 and s < w1]

    def busy_intervals(self, device) -> list:
        w0, w1 = self.window
        iv = sorted((max(s, w0), min(e, w1)) for _, s, e in self.ops[device]
                    if e > w0 and s < w1)
        merged = []
        for s, e in iv:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return merged

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over chips."""
        if not self.ops:
            return 0.0
        tot = sum(sum(e - s for s, e in self.busy_intervals(d))
                  for d in self.ops)
        return tot / len(self.ops) * 1e-9

    def op_seconds(self, match=None) -> dict:
        """Device seconds per operation (summed over calls and chips),
        optionally only ops whose text ``match`` accepts. Each instant
        of busy time is charged to the op that started last among those
        running (a loop's body ops, not the loop), so the values add up
        to the busy time."""
        import heapq
        out = defaultdict(float)
        w0, w1 = self.window
        for dev in self.ops:
            evs = [(max(s, w0), min(e, w1), n)
                   for n, s, e in self.ops[dev] if e > w0 and s < w1]
            points = sorted([(s, 1, i) for i, (s, _, _) in enumerate(evs)]
                            + [(e, 0, i) for i, (_, e, _) in enumerate(evs)])
            heap, ended, prev = [], set(), None
            for t, kind, i in points:
                while heap and -heap[0][1] in ended:
                    heapq.heappop(heap)
                if heap and prev is not None and t > prev:
                    out[evs[-heap[0][1]][2]] += t - prev
                if kind:
                    heapq.heappush(heap, (-evs[i][0], -i))
                else:
                    ended.add(i)
                prev = t
        res = defaultdict(float)
        for n, ns in out.items():
            if match is None or match(n):
                res[short_name(n)] += ns * 1e-9
        return dict(res)

    def kernel_calls(self, accept) -> list:
        """(seconds, results, operands) of every Pallas kernel call in
        the window whose shapes ``accept(results, operands)`` takes."""
        out = []
        for _, n, s, e in self.device_ops():
            cc = custom_call(n)
            if cc is not None and accept(*cc):
                out.append(((e - s) * 1e-9, cc[0], cc[1]))
        return out

    def excerpt(self, t0: int, t1: int) -> dict:
        """The part of the trace between t0 and t1 (ns) as plain JSON, in
        the planes/lines/events layout ``from_profile`` reads."""
        planes = []
        for dev, evs in self.ops.items():
            planes.append({"name": dev, "lines": [{"name": "XLA Ops",
                "events": [[n, s, e] for n, s, e in evs
                           if s < t1 and e > t0]}]})
        host = [[n, s, e] for n, s, e in self.host
                if (s < t1 and e > t0) or n == WINDOW_SPAN]
        planes.append({"name": "/host:CPU",
                       "lines": [{"name": "host", "events": host}]})
        return {"planes": planes}

    def idle_gaps(self, top: int = 10) -> list:
        """The longest idle stretches of the first chip inside the
        window, each as [what the host was doing, seconds]: the name of
        the shortest host span covering most of the gap."""
        if not self.ops:
            return []
        dev = sorted(self.ops)[0]
        w0, w1 = self.window
        gaps, t = [], w0
        for s, e in self.busy_intervals(dev):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if w1 > t:
            gaps.append((t, w1))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for g0, g1 in gaps[:top]:
            out.append([self._host_name(g0, g1), (g1 - g0) * 1e-9])
        return out

    def _host_name(self, g0, g1) -> str:
        best, span = None, None
        for n, s, e in self.host:
            if n == WINDOW_SPAN:
                continue
            ov = min(e, g1) - max(s, g0)
            if ov <= 0 or ov < 0.5 * (g1 - g0):
                continue
            if span is None or e - s < span:
                best, span = n, e - s
        return best or "no host span"


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def _op_line(line_name: str) -> bool:
    return line_name == "XLA Ops"


def load(directory: str) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {directory}")
    return from_profile(ProfileData.from_file(max(files,
                                                  key=os.path.getmtime)))


class _Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def from_json(path: str) -> Trace:
    """A trace excerpt written by ``Trace.excerpt`` (optionally gzip)."""
    import gzip
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        raw = json.load(f)
    planes = [_Obj(name=p["name"], lines=[
        _Obj(name=ln["name"], events=[
            _Obj(name=n, start_ns=s, end_ns=e) for n, s, e in ln["events"]])
        for ln in p["lines"]]) for p in raw["planes"]]
    return from_profile(_Obj(planes=planes))


def from_profile(pd) -> Trace:
    ops, host, window = {}, [], None
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            evs = []
            for line in plane.lines:
                if _op_line(line.name):
                    evs += [(e.name, e.start_ns, e.end_ns)
                            for e in line.events]
            if evs:
                ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append((e.name, e.start_ns, e.end_ns))
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.end_ns)
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    return Trace(window=window, ops=ops, host=host)
