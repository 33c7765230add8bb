"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

- busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:...`` plane), averaged over the
  devices that ran anything; idle share is 1 - busy / window;
- module time: the ``XLA Modules`` events of one compiled program
  (``jit_<name>``), each one execution; an op belongs to the execution
  whose span holds it;
- breakdown: the operations that took most device time, and the longest
  idle gaps named by the host event that overlaps each the most.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

#: host events that only bracket thread-pool work; they name no cause
_GENERIC_HOST = re.compile(r"^(ThreadpoolListener::|\$)")
#: "%fusion.3 = u8[4096]{...} fusion(...)" -> "%fusion.3 = u8[4096]"
_OP_HEAD = re.compile(r"^(%\S+ = [^{ ]+)")


@dataclass
class Op:
    module: str
    name: str  # the trace's name: the op's HLO text
    start: float  # seconds from the start of the trace
    dur: float
    run: int  # index of the module execution that holds it, or -1


@dataclass
class Trace:
    window_s: float
    devices: dict = field(default_factory=dict)  # plane name -> [Op]
    #: plane name -> [(module, start, dur)], one per program execution
    modules: dict = field(default_factory=dict)
    host: list = field(default_factory=list)  # (name, start, dur)

    # -- device time -----------------------------------------------------------

    def busy_intervals(self, plane: str) -> np.ndarray:
        ops = self.devices[plane]
        if not ops:
            return np.zeros((0, 2))
        iv = np.array(sorted((o.start, o.start + o.dur) for o in ops))
        merged = [list(iv[0])]
        for a, b in iv[1:]:
            if a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return np.array(merged)

    def busy_s(self) -> float:
        """Device-busy seconds, averaged over the devices that ran ops."""
        per = [float(np.sum(iv[:, 1] - iv[:, 0]))
               for iv in map(self.busy_intervals, self.devices)
               if len(iv)]
        return float(np.mean(per)) if per else 0.0

    def module_runs(self, module: str) -> list:
        """(start, dur) of each execution of program ``module`` (a name
        with or without its ``jit_`` prefix), over all devices."""
        want = {module, "jit_" + module}
        return [(s, d) for runs in self.modules.values()
                for m, s, d in runs if m in want]

    def module_spans(self, module: str) -> dict:
        """(plane, run) -> device seconds of each execution of
        ``module``."""
        want = {module, "jit_" + module}
        return {(plane, i): d for plane, runs in self.modules.items()
                for i, (m, _, d) in enumerate(runs) if m in want}

    def module_ops(self, module: str) -> dict:
        """Ops of each execution of program ``module``: run -> [Op]."""
        want = {module, "jit_" + module}
        out: dict = defaultdict(list)
        for plane, ops in self.devices.items():
            for o in ops:
                if o.module in want:
                    out[(plane, o.run)].append(o)
        return dict(out)

    # -- breakdown -------------------------------------------------------------

    def top_ops(self, k: int = 10) -> list:
        """The k (program/op, seconds) that took most device time; an op
        is named by its HLO instruction and result shape."""
        total = defaultdict(float)
        for ops in self.devices.values():
            for o in ops:
                m = _OP_HEAD.match(o.name)
                total[f"{o.module}/{m.group(1) if m else o.name}"] += o.dur
        return sorted(total.items(), key=lambda kv: -kv[1])[:k]

    def idle_gaps(self, k: int = 10) -> list:
        """The k longest idle gaps of the first busy device, each named by
        the host event that overlaps it the most."""
        planes = [p for p in self.devices if self.devices[p]]
        if not planes:
            return []
        iv = self.busy_intervals(planes[0])
        edges = np.concatenate([[0.0], iv.ravel(), [self.window_s]])
        starts, ends = edges[0::2], edges[1::2]
        gaps = sorted(
            ((float(b - a), float(a), float(b)) for a, b in zip(starts, ends)
             if b > a),
            reverse=True,
        )[:k]
        out = []
        for length, a, b in gaps:
            over = defaultdict(float)
            for name, hs, hd in self.host:
                o = min(b, hs + hd) - max(a, hs)
                if o > 0 and not _GENERIC_HOST.match(name):
                    over[name] += o
            cause = max(over.items(), key=lambda kv: kv[1])[0] if over \
                else "no host event"
            out.append([cause, length])
        return out


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _module_of(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name).strip()


def load(path: str, window_s: float | None = None) -> Trace:
    """Read ``path``. Event times are offsets from the start of the
    trace; the window is the trace's own span (``Task Environment``),
    else ``window_s``, else the span of the device ops."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace(window_s=0.0)
    for plane in data.planes:
        lines = {line.name for line in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            modules, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = sorted(
                        (e.start_ns * 1e-9, e.duration_ns * 1e-9,
                         _module_of(e.name)) for e in line.events)
                elif line.name == "XLA Ops":
                    ops = [(e.start_ns * 1e-9, e.duration_ns * 1e-9, e.name)
                           for e in line.events]
            starts = np.array([m[0] for m in modules])
            out = []
            for s, d, name in ops:
                i = int(np.searchsorted(starts, s, side="right")) - 1
                if i >= 0 and s < modules[i][0] + modules[i][1] + 1e-9:
                    out.append(Op(modules[i][2], name, s, d, i))
                else:
                    out.append(Op("", name, s, d, -1))
            trace.devices[plane.name] = out
            trace.modules[plane.name] = [(m, s, d) for s, d, m in modules]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.host += [(e.name, e.start_ns * 1e-9,
                                e.duration_ns * 1e-9)
                               for e in line.events if e.duration_ns > 0]
        elif plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                window_s = (st["profile_stop_time"]
                            - st["profile_start_time"]) * 1e-9
    if window_s is None:
        ends = [o.start + o.dur for ops in trace.devices.values()
                for o in ops]
        window_s = max(ends) if ends else 0.0
    trace.window_s = float(window_s)
    return trace
