"""The device as a profiler trace shows it, reduced to numbers.

A traced run records a few seconds of its window with ``jax.profiler`` and
marks what the host was doing with the benchmark's own annotations
(``bench:<name>``). The reduction works on plain events (plane, line, name,
start, end in nanoseconds) so that it can be checked on a small recorded
trace without a chip:

* busy: the union of the intervals in which an operation ran on a device,
  within the traced window, averaged over the devices;
* in flight: the traced window less the host's ``bench:wait`` spans (the
  loop had nothing to give the engine);
* per step program and per kernel: device time, matched by name (XLA
  module names such as ``jit__ragged_step_fn(...)``; a Pallas kernel is a
  ``tpu_custom_call`` operation inside its step program's module, since the
  program gives its kernels no names of their own);
* per launch of a step program: its time on each device that ran it, so
  that a program partitioned over several devices is one step, not one per
  device;
* idle gaps: each gap in the busy union, labelled with the host span that
  covers most of it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench:traced"
WAIT_SPAN = "bench:wait"
# spans that hold finer ones: a gap goes to them only where no finer span
# covers any of it
COARSE_SPANS = (WINDOW_SPAN, "bench:engine.step")


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: int   # ns
    end: int     # ns


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def load_xplane(path: str) -> List[Event]:
    """Every event of a recorded ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = []
    for pl in ProfileData.from_file(path).planes:
        for ln in pl.lines:
            for ev in ln.events:
                start = int(ev.start_ns)
                out.append(Event(pl.name, ln.name, ev.name, start,
                                 start + int(ev.duration_ns)))
    return out


def newest_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def merge(iv: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for lo, hi in sorted(iv):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def clip_len(iv: Sequence[Tuple[int, int]], lo: int, hi: int) -> int:
    return sum(max(0, min(b, hi) - max(a, lo)) for a, b in iv)


def _subtract(base: List[Tuple[int, int]], cut: List[Tuple[int, int]]
              ) -> List[Tuple[int, int]]:
    out = []
    for a, b in base:
        cur = a
        for c, d in cut:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
        if cur < b:
            out.append((cur, b))
    return out


def _line(events: Sequence[Event], plane: str, want: str) -> List[Event]:
    return [e for e in events if e.plane == plane and e.line == want]


@dataclass
class Reduced:
    window: Tuple[int, int]
    devices: List[str]
    busy_s: float                    # mean over devices, inside the window
    in_flight_s: float
    busy_in_flight_s: float          # mean over devices
    modules: Dict[str, Dict[str, List[float]]]  # module -> device -> seconds per run
    ops: Dict[str, float]            # op label -> self seconds
    kernels: Dict[str, float]        # kernel name -> seconds
    kernel_calls: Dict[str, int]
    gaps: List[Tuple[str, float]]    # longest idle gaps, by host span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


_NUM = re.compile(r"[.\-_]?\d+$")


def op_label(name: str) -> str:
    """An operation's HLO name without its number: ``%fusion.126 = ...`` is
    ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return _NUM.sub("", head)


def _self_times(ops: List[Event], lo: int, hi: int) -> List[int]:
    """Each operation's time inside [lo, hi) less that of the operations
    nested inside it (a loop op holds its body's ops on the same line)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))

    def inside(e):
        return max(0, min(e.end, hi) - max(e.start, lo))

    own = [inside(e) for e in ops]
    stack: List[int] = []
    for i in order:
        while stack and ops[stack[-1]].end <= ops[i].start:
            stack.pop()
        if stack and ops[i].end <= ops[stack[-1]].end:
            own[stack[-1]] -= inside(ops[i])
        stack.append(i)
    return own


def reduce(events: Sequence[Event], kernels: Dict[str, Dict[str, str]] = None,
           n_gaps: int = 10) -> Reduced:
    """Reduce a trace's events. ``kernels`` names each kernel by the step
    program (module name fragment) whose operations hold it and a fragment
    of the operation's name (a Pallas kernel is a ``tpu_custom_call``)."""
    kernels = kernels or {}
    win = [e for e in events if not is_device(e.plane) and e.name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = win[0].start, win[0].end
    devices = sorted({e.plane for e in events if is_device(e.plane)})
    if not devices:
        raise ValueError("no device plane in the trace")
    host = [e for e in events if not is_device(e.plane)
            and e.name.startswith("bench:") and e.end > lo and e.start < hi]
    waits = merge([(max(e.start, lo), min(e.end, hi)) for e in host
                   if e.name == WAIT_SPAN])
    flight = _subtract([(lo, hi)], waits)
    in_flight = sum(b - a for a, b in flight)
    busy, busy_fl = [], []
    modules: Dict[str, Dict[str, List[float]]] = {}
    ops: Dict[str, float] = {}
    kern = {k: 0.0 for k in kernels}
    kcalls = {k: 0 for k in kernels}
    union0: List[Tuple[int, int]] = []
    for dev in devices:
        dev_ops = [e for e in _line(events, dev, "XLA Ops") if e.end > lo and e.start < hi]
        if not dev_ops:
            raise ValueError(f"no operations on {dev} in the traced window")
        union = merge([(e.start, e.end) for e in dev_ops])
        if dev == devices[0]:
            union0 = union
        busy.append(clip_len(union, lo, hi))
        busy_fl.append(sum(clip_len(union, a, b) for a, b in flight))
        mods = sorted(_line(events, dev, "XLA Modules"), key=lambda e: e.start)
        for e in mods:
            if e.start >= lo and e.end <= hi:
                modules.setdefault(e.name, {}).setdefault(dev, []).append(
                    (e.end - e.start) / 1e9)
        starts = [m.start for m in mods]
        for e, own in zip(dev_ops, _self_times(dev_ops, lo, hi)):
            i = bisect.bisect_right(starts, e.start) - 1
            module = mods[i].name if i >= 0 and mods[i].end >= e.end else ""
            label = op_label(e.name)
            for k, where in kernels.items():
                if where["module"] in module and where["op"] in e.name:
                    label = k
                    kern[k] += (min(e.end, hi) - max(e.start, lo)) / 1e9
                    kcalls[k] += 1
            ops[label] = ops.get(label, 0.0) + own / 1e9
    n = len(devices)
    gaps = []
    edges = [(lo, lo)] + [iv for iv in union0 if iv[1] > lo and iv[0] < hi] + [(hi, hi)]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        a, b = max(a, lo), min(b, hi)
        if b - a >= 1000:        # shorter gaps are the ops' own seams
            gaps.append((a, b))
    labelled = [(_label(host, a, b), (b - a) / 1e9)
                for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n_gaps]]
    return Reduced(window=(lo, hi), devices=devices, busy_s=sum(busy) / n / 1e9,
                   in_flight_s=in_flight / 1e9, busy_in_flight_s=sum(busy_fl) / n / 1e9,
                   modules=modules, ops=ops, kernels=kern, kernel_calls=kcalls,
                   gaps=labelled)


def _label(host: Sequence[Event], a: int, b: int) -> str:
    """What the host was doing in the idle gap [a, b): the finer span that
    overlaps it most, else the engine step, else "other"."""
    cover: Dict[str, int] = {}
    for e in host:
        o = min(e.end, b) - max(e.start, a)
        if o > 0 and e.name != WINDOW_SPAN:
            cover[e.name] = cover.get(e.name, 0) + o
    fine = {k: v for k, v in cover.items() if k not in COARSE_SPANS}
    pick = fine or cover
    if not pick:
        return "other"
    return max(pick, key=lambda k: pick[k])[len("bench:"):]


def module_times(red: Reduced, fragments) -> List[float]:
    """Device seconds of every run of the modules whose names hold one of
    ``fragments``, on every device."""
    return [t for runs in launches(red, fragments) for t in runs]


def launches(red: Reduced, fragments) -> List[List[float]]:
    """Device seconds of each launch of the modules whose names hold one of
    ``fragments``, one entry per device that ran it. A module's name carries
    its program's fingerprint, and each device runs one program's launches
    in order, so the k-th run of a module on every device that runs it is
    its k-th launch. The traced window opens and closes with the device
    idle, so no launch is cut at its edges."""
    frags = list(fragments)
    out: List[List[float]] = []
    for name, per_dev in red.modules.items():
        if any(f in name for f in frags):
            n = max(len(ts) for ts in per_dev.values())
            out.extend([ts[k] for ts in per_dev.values() if k < len(ts)] for k in range(n))
    return out


def breakdown(red: Reduced, n: int = 10) -> Dict[str, list]:
    """The ``breakdown`` of a traced run's result line."""
    top = sorted(red.ops.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in red.gaps[:n]]}
