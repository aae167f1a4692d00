"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Read with ``jax.profiler.ProfileData`` alone. Device planes are
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation executed, carrying its program in the ``hlo_module`` stat, and
``XLA Modules`` one event per program execution. Host planes hold the
harness's ``TraceAnnotation`` spans (``hooks.SPANS`` and the KB call).

  * the traced window: from the end of the profiler's ``start_trace`` host
    event to the start of its ``stop_trace`` event (the extent of all
    events where those are absent);
  * busy: the union of the device's ``XLA Ops`` intervals inside it;
  * per program: device seconds and executions, by program name with the
    ``jit_`` prefix and any ``(id)`` suffix dropped;
  * per operation: device seconds by ``program:operation``, the operation
    named by its HLO instruction name (a Pallas kernel by its custom call's
    name) and placed in the program whose execution contains it;
  * idle gaps: each interval between busy intervals, named by the harness
    span that overlaps it most (``host: other`` where none does), summed by
    name.

Device timestamps can run ahead of the host's (about a millisecond on a
v5e): they are shifted by the least amount that puts every program
execution after the host's ``DoEnqueueProgram`` of the same ``run_id``."""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE = re.compile(r"^/device:TPU:\d+$")
OP = re.compile(r"^%?([^\s=]+)\s*=")
HARNESS_SPANS = ("engine.decode", "engine.prefill", "retriever.kb_call",
                 "knnlm.interpolate")


def program_name(raw: str) -> str:
    name = re.sub(r"\(\d+\)$", "", raw or "").strip()
    return name[4:] if name.startswith("jit_") else name


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


@dataclass
class Summary:
    window_s: float = 0.0
    busy_s: float = 0.0                  # averaged over device planes
    n_devices: int = 0
    programs: Dict[str, List[float]] = field(default_factory=dict)  # name -> [s, n]
    ops: Dict[str, float] = field(default_factory=dict)
    gaps: Dict[str, float] = field(default_factory=dict)

    def program_s(self, name: str) -> float:
        return self.programs.get(name, [0.0, 0])[0]

    def program_n(self, name: str) -> int:
        return int(self.programs.get(name, [0.0, 0])[1])

    def breakdown(self, n: int = 10) -> dict:
        top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                                   key=lambda kv: -kv[1])[:n]]
        return {"device_ops": top(self.ops), "idle_gaps": top(self.gaps)}


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def op_name(hlo: str) -> str:
    m = OP.match(hlo or "")
    return m.group(1) if m else (hlo or "?")[:60]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce(pd) -> Summary:
    """Summary of a ``ProfileData``."""
    dev_planes, spans, lo, hi = [], [], float("inf"), float("-inf")
    t_open, t_close = None, None
    enqueue = {}
    for plane in pd.planes:
        is_dev = bool(DEVICE.match(plane.name))
        if is_dev:
            dev_planes.append(plane)
        for line in plane.lines:
            for ev in line.events:
                lo, hi = min(lo, ev.start_ns), max(hi, ev.end_ns)
                if is_dev:
                    continue
                if ev.name in HARNESS_SPANS:
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
                elif ev.name == "DoEnqueueProgram":
                    rid = dict(ev.stats).get("run_id")
                    if rid is not None:
                        enqueue[int(rid)] = ev.start_ns
                elif ev.name.endswith(" start_trace"):
                    t_open = ev.end_ns
                elif ev.name.endswith(" stop_trace"):
                    t_close = ev.start_ns
    lo = t_open if t_open is not None else lo
    hi = t_close if t_close is not None else hi
    s = Summary(n_devices=len(dev_planes))
    if not dev_planes or hi <= lo:
        return s
    s.window_s = (hi - lo) * 1e-9
    programs = defaultdict(lambda: [0.0, 0])
    ops = defaultdict(float)
    gaps = defaultdict(float)
    busy_total = 0.0
    spans.sort()
    for plane in dev_planes:
        lines = {l.name: l for l in plane.lines}
        raw = [(ev.start_ns, ev.end_ns, program_name(ev.name),
                dict(ev.stats).get("run_id")) for ev in
               (lines["XLA Modules"].events if "XLA Modules" in lines else ())]
        sh = _shift(raw, enqueue)
        mods = []
        for a, b, name, _ in raw:
            a, b = a + sh, b + sh
            if b <= lo or a >= hi:
                continue
            p = programs[name]
            p[0] += (b - a) * 1e-9
            p[1] += 1
            mods.append((a, b, name))
        mods.sort()
        starts = [m[0] for m in mods]
        op_iv = []
        for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
            a, b = ev.start_ns + sh, ev.end_ns + sh
            if b <= lo or a >= hi:
                continue
            i = bisect.bisect_right(starts, a) - 1
            prog = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            ops[f"{prog}:{op_name(ev.name)}"] += ev.duration_ns * 1e-9
            op_iv.append((a, b))
        busy = _union(_clip(op_iv or [m[:2] for m in mods], lo, hi))
        busy_total += sum(e - b for b, e in busy) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps[_span_at(spans, g0, g1)] += (g1 - g0) * 1e-9
    s.busy_s = busy_total / len(dev_planes)
    s.programs = dict(programs)
    s.ops = dict(ops)
    s.gaps = dict(gaps)
    return s


def _shift(mods, enqueue) -> float:
    """Nanoseconds to add to device times so that no execution starts
    before the host enqueued it (never negative)."""
    lead = [enqueue[int(r)] - a for a, _, _, r in mods
            if r is not None and int(r) in enqueue]
    return max([0.0, *lead])


def _span_at(spans, g0: float, g1: float, reach: int = 64) -> str:
    """The harness span overlapping [g0, g1) the most (spans sorted by
    start; the ``reach`` latest starting before g1 are considered)."""
    best, name = 0.0, "host: other"
    i = bisect.bisect_left(spans, (g1,))
    for s0, s1, n in spans[max(0, i - reach):i]:
        ov = min(s1, g1) - max(s0, g0)
        if ov > best:
            best, name = ov, n
    return name


def load(log_dir: str) -> Summary:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(log_dir)))
