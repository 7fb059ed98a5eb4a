"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics and the ``breakdown`` read.

* the traced window: from the start of the first to the end of the last
  ``harness:*`` annotation (the harness is inside one at every moment
  of its loop), on the trace's own clock;
* device busy time: the union of the intervals in which an operation ran
  on the device (the ``XLA Ops`` line of each ``/device:TPU:<n>``
  plane), clipped to the window and averaged over the devices;
* device time and launches per executable (the ``XLA Modules`` line,
  whose events the TPU names ``<module>(<program id>)``);
* the operations that took most device time, by the name the trace
  gives them;
* the longest idle gaps, each labelled with the harness annotation that
  covers most of it (what the host was doing meanwhile).
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "harness:"
#: entries in each list of the breakdown
TOP = 10


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start_ns: float
    dur_ns: float
    stats: Tuple[Tuple[str, str], ...] = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                      # averaged over devices
    n_devices: int
    exe_time_s: Dict[str, float]       # summed over devices
    exe_launches: Dict[str, int]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def op_name(name: str) -> str:
    """An op's name: the trace names a TPU op by its whole HLO
    instruction (``%fusion.10 = bf16[...] fusion(...)``); keep
    ``fusion.10``."""
    return name.split(" = ", 1)[0].lstrip("%")


def exe_key(ev: Ev) -> str:
    """An executable's key: its module name and, where the trace gives
    one, its program id."""
    stats = dict(ev.stats)
    pid = stats.get("program_id")
    name = stats.get("hlo_module") or ev.name
    return f"{name}#{pid}" if pid is not None else name


def _union(intervals: Sequence[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _label(gap: Tuple[float, float], notes: Sequence[Ev]) -> str:
    best, best_ov = "none", 0.0
    for n in notes:
        ov = min(gap[1], n.end_ns) - max(gap[0], n.start_ns)
        if ov > best_ov:
            best, best_ov = n.name, ov
    return best


def reduce_events(ops: Dict[str, List[Ev]], modules: Dict[str, List[Ev]],
                  notes: List[Ev]) -> TraceSummary:
    """``ops`` and ``modules``: device name -> its events; ``notes``: the
    harness's annotations."""
    if notes:
        lo = min(n.start_ns for n in notes)
        hi = max(n.end_ns for n in notes)
    else:
        every = [e for evs in ops.values() for e in evs]
        lo = min(e.start_ns for e in every)
        hi = max(e.end_ns for e in every)
    busy_ns, gaps = 0.0, []
    for dev, evs in ops.items():
        iv = _clip(_union([(e.start_ns, e.end_ns) for e in evs]), lo, hi)
        busy_ns += sum(b - a for a, b in iv)
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    n_dev = max(len(ops), 1)
    exe_t: Dict[str, float] = collections.defaultdict(float)
    exe_n: Dict[str, int] = collections.defaultdict(int)
    for evs in modules.values():
        for e in evs:
            if lo <= e.start_ns < hi:
                exe_t[exe_key(e)] += e.dur_ns * 1e-9
                exe_n[exe_key(e)] += 1
    op_t: Dict[str, float] = collections.defaultdict(float)
    for evs in ops.values():
        for e in evs:
            if lo <= e.start_ns < hi:
                op_t[op_name(e.name)] += e.dur_ns * 1e-9
    top = sorted(op_t.items(), key=lambda kv: -kv[1])[:TOP]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    idle = [(_label(g, notes), (g[1] - g[0]) * 1e-9) for g in longest]
    return TraceSummary(window_s=(hi - lo) * 1e-9,
                        busy_s=busy_ns * 1e-9 / n_dev, n_devices=len(ops),
                        exe_time_s=dict(exe_t), exe_launches=dict(exe_n),
                        top_ops=top, idle_gaps=idle)


def _ev(e, stats: bool = False) -> Ev:
    return Ev(name=e.name, start_ns=float(e.start_ns),
              dur_ns=float(e.duration_ns),
              stats=tuple((str(k), str(v)) for k, v in e.stats)
              if stats else ())


def load(path: str):
    """(ops, modules, notes) from an ``.xplane.pb`` file (or its gzip)."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        import gzip
        with gzip.open(path) as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(str(path))
    ops: Dict[str, List[Ev]] = {}
    modules: Dict[str, List[Ev]] = {}
    notes: List[Ev] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [_ev(e) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [_ev(e, stats=True)
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                notes += [_ev(e) for e in line.events
                          if e.name.startswith(ANNOTATION_PREFIX)]
    return ops, modules, notes


def reduce_file(path: str) -> Optional[TraceSummary]:
    """The summary of one trace file; None where no device op ran."""
    ops, modules, notes = load(path)
    if not any(ops.values()):
        return None
    return reduce_events(ops, modules, notes)


def find_trace(directory: str) -> Optional[str]:
    """The ``.xplane.pb`` that ``jax.profiler`` wrote under a directory."""
    import glob
    found = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    return found[-1] if found else None
