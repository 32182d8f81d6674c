"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

On a TPU each device plane (``/device:TPU:<n>``) has an ``XLA Modules``
line, one event per run of a compiled program, and an ``XLA Ops`` line,
one event per HLO operation, named by the operation's HLO text
(``%paged_flash_mq.24 = f32[4,32,1,128]... custom-call(...)``).  Ops
nest: a ``while`` loop's event holds its body's ops.  The ``Async XLA
Ops`` line (copies in flight beside compute) is left out.  An op's
module is the ``XLA Modules`` event that holds its start.

A trace of the CPU backend has no device plane; there the events that
carry ``hlo_op`` and ``hlo_module`` stats, which XLA's CPU client
writes on its own threads, stand in for both lines, so the reduction
can be tested without a chip.

The window runs from the start of the first of the harness's host spans
named ``WINDOW_SPAN`` (one per ``generate`` call) to the end of the
last; ``GAP_SPAN`` marks the host's time between calls.  Busy time is
the union of op intervals inside the window, per device, averaged over
the devices.  An op's self time is its time less that of the ops it
holds.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.generate"
GAP_SPAN = "bench.between"


@dataclasses.dataclass
class Op:
    device: str
    name: str                    # short HLO name, e.g. "paged_flash_mq.24"
    module: str
    start: int                   # ns
    end: int
    self_ns: float = 0.0         # inside the window, less nested ops


@dataclasses.dataclass
class Reduced:
    window: Tuple[int, int]      # ns
    devices: List[str]
    busy_ns: float               # union of op intervals, mean over devices
    module_ns: Dict[str, float]  # program time per module, all devices
    op_ns: Dict[str, float]      # self time per "module/op", all devices
    ops: List[Op]
    gaps: List[Tuple[str, float]]  # (host span, seconds), longest first

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def n_devices(self) -> int:
        return max(1, len(self.devices))

    def module_time(self, pred) -> float:
        """Seconds of program time, summed over devices, of the modules
        whose name satisfies ``pred``."""
        return sum(v for k, v in self.module_ns.items() if pred(k)) / 1e9

    def op_time(self, pred) -> float:
        """Seconds of self time, summed over devices, of the ops for which
        ``pred(op)`` holds."""
        return sum(o.self_ns for o in self.ops if pred(o)) / 1e9

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v / 1e9] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def short_name(hlo_text: str) -> str:
    """``%while.71 = (s32[], ...) while(...)`` -> ``while.71``."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(a: int, b: int, lo: int, hi: int) -> int:
    return max(0, min(b, hi) - max(a, lo))


def _set_self_times(ops: List[Op], lo: int, hi: int) -> None:
    """Self time of each op of one device: its clipped time less the
    clipped time of the ops directly inside it."""
    stack: List[Op] = []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1].end <= o.start:
            stack.pop()
        o.self_ns += _clip(o.start, o.end, lo, hi)
        if stack and o.end <= stack[-1].end:
            stack[-1].self_ns -= _clip(o.start, o.end, lo, hi)
        stack.append(o)


def _module_of(start: int, modules: List[Tuple[int, int, str]],
               starts: List[int]) -> str:
    i = bisect.bisect_right(starts, start) - 1
    if i >= 0 and modules[i][0] <= start < modules[i][1]:
        return modules[i][2]
    return "?"


def reduce_profile(pd) -> Reduced:
    """``pd``: a ``jax.profiler.ProfileData``, or anything shaped alike."""
    ops: List[Op] = []
    mods: List[Tuple[str, int, int, str]] = []     # device, start, end, name
    spans: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:")
        modules: List[Tuple[int, int, str]] = []
        if is_dev:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = sorted((int(e.start_ns), int(e.end_ns), e.name)
                                     for e in line.events)
            mods += [(plane.name, a, b, n) for a, b, n in modules]
        starts = [m[0] for m in modules]
        for line in plane.lines:
            if is_dev and line.name != "XLA Ops":
                continue
            for ev in line.events:
                if ev.name in (WINDOW_SPAN, GAP_SPAN):
                    spans.append((ev.name, int(ev.start_ns), int(ev.end_ns)))
                    continue
                if is_dev:
                    a = int(ev.start_ns)
                    ops.append(Op(plane.name, short_name(ev.name),
                                  _module_of(a, modules, starts), a,
                                  int(ev.end_ns)))
                    continue
                st = _stats(ev)
                if "hlo_op" in st and "hlo_module" in st:
                    a, b = int(ev.start_ns), int(ev.end_ns)
                    ops.append(Op("cpu", str(st["hlo_op"]),
                                  str(st["hlo_module"]), a, b))
                    mods.append(("cpu", a, b, str(st["hlo_module"])))
    calls = sorted((a, b) for n, a, b in spans if n == WINDOW_SPAN)
    if not calls:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = calls[0][0], max(b for _, b in calls)

    per_dev: Dict[str, List[Op]] = defaultdict(list)
    for o in ops:
        per_dev[o.device].append(o)
    unions, op_ns = {}, defaultdict(float)
    for dev, dops in per_dev.items():
        _set_self_times(dops, lo, hi)
        iv = [(max(o.start, lo), min(o.end, hi)) for o in dops
              if _clip(o.start, o.end, lo, hi) > 0]
        if iv:
            unions[dev] = _union(iv)
        for o in dops:
            if o.self_ns > 0:
                op_ns[f"{o.module}/{o.name}"] += o.self_ns
    busy = (sum(sum(b - a for a, b in u) for u in unions.values())
            / len(unions)) if unions else 0.0
    module_ns: Dict[str, float] = defaultdict(float)
    if any(d != "cpu" for d, *_ in mods):
        for d, a, b, n in mods:
            module_ns[n] += _clip(a, b, lo, hi)
    else:                        # CPU: a module's time is its ops' union
        by_mod: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
        for o in ops:
            if _clip(o.start, o.end, lo, hi) > 0:
                by_mod[o.module].append((max(o.start, lo), min(o.end, hi)))
        for n, iv in by_mod.items():
            module_ns[n] = sum(b - a for a, b in _union(iv))
    module_ns = {k: v for k, v in module_ns.items() if v > 0}
    return Reduced(window=(lo, hi), devices=sorted(unions), busy_ns=busy,
                   module_ns=module_ns, op_ns=dict(op_ns), ops=ops,
                   gaps=_idle_gaps(unions, spans, lo, hi))


def _idle_gaps(unions, spans, lo, hi) -> List[Tuple[str, float]]:
    """Idle stretches of the first device inside the window, longest
    first, each named by the host span that holds its midpoint: inside
    a ``generate`` call (the program's host work) or between calls (the
    harness's)."""
    if not unions:
        return [("no device op", (hi - lo) / 1e9)]
    busy = unions[sorted(unions)[0]]
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    host = sorted((a, b, n) for n, a, b in spans)
    starts = [h[0] for h in host]
    out = []
    for i in range(0, len(edges), 2):
        a, b = edges[i], edges[i + 1]
        if b <= a:
            continue
        mid = (a + b) // 2
        j = bisect.bisect_right(starts, mid) - 1
        label = host[j][2] if j >= 0 and mid < host[j][1] else \
            "untraced host time"
        out.append((label, (b - a) / 1e9))
    return sorted(out, key=lambda g: -g[1])


def reduce_dir(trace_dir: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)))


def module_is(*names: str):
    """Predicate on a module name: it holds one of ``names``
    (``jit__edge_prefill_impl`` and ``jit__edge_prefill_impl(3)`` alike)."""
    def pred(module: str) -> bool:
        return any(n in module for n in names)
    return pred


def op_is(*names: str):
    """Predicate on an op: its short name holds one of ``names``."""
    def pred(o: Op) -> bool:
        return any(n in o.name for n in names)
    return pred


def share(part: float, whole: float) -> Optional[float]:
    return None if whole <= 0 or part <= 0 else 100.0 * part / whole
