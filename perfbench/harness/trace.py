"""Reduce a profiler trace to what the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  On a TPU,
each ``/device:TPU:<i>`` plane holds an ``XLA Modules`` line (one event
per program run) and an ``XLA Ops`` line (one event per HLO instruction
run, named by the instruction's text, loops enclosing their bodies'
ops); the host planes hold this benchmark's own ``perfbench.*`` spans.
The ops carry no scope of their own: the round program's compiled HLO
text gives each instruction its ``op_name`` metadata, where
``repro.obs.trace.stage`` has put the ``round.<stage>`` scope, and the
``XLA Modules`` events tell which program an op ran in.

* busy time: the union of the device-operation intervals inside the
  window, averaged over the devices,
* idle share: 1 - busy / window,
* stage time: the ops' self time (their duration less that of the ops
  nested in them) summed by the first ``round.<stage>`` scope of their
  metadata; ops of the round under no such scope count as ``""``, ops of
  the benchmark's batch generator as ``traffic``,
* kernel time: summed durations of the Pallas kernel launches (custom
  calls) named after the kernel,
* idle gaps: the gaps between busy intervals, each named after the host
  span that covers most of it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import NamedTuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "perfbench.window"
TRAFFIC = "perfbench_traffic"
_STAGE = re.compile(r"round\.[A-Za-z_]+")
_EVENT = re.compile(r"^%?([\w.\-]+) = .*?\b([a-z][\w\-]*)\(")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|body|to_apply)=%?([\w.\-]+)")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_MODULE = re.compile(r"^\s*HloModule\s+([\w.\-]+)")


class Op(NamedTuple):
    device: str
    name: str               # HLO instruction name, e.g. "fusion.102"
    opcode: str             # e.g. "fusion", "custom-call", "while"
    start: int              # ns
    end: int                # ns
    meta: str               # op_name metadata, or the program's name


class Span(NamedTuple):
    name: str
    start: int
    end: int


def parse_event(text: str):
    """(instruction name, opcode) of an ``XLA Ops`` event's name."""
    m = _EVENT.match(text)
    return (m.group(1), m.group(2)) if m else (text.split(" ")[0], "")


def op_names(hlo_text: str):
    """(module name, {instruction: op_name}) from a compiled program's HLO
    text; an instruction without metadata of its own takes that of the
    first instruction of the computation it calls (a fusion's, a loop
    body's)."""
    module, comp = "", ""
    first: dict = {}
    names: dict = {}
    calls: dict = {}
    for line in hlo_text.splitlines():
        m = _MODULE.match(line)
        if m:
            module = m.group(1)
            continue
        m = _COMP.match(line)
        if m and "=" not in line.split("(")[0]:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OPNAME.search(line)
        if op:
            names[m.group(1)] = op.group(1)
            first.setdefault(comp, op.group(1))
        else:
            c = _CALLS.search(line)
            if c:
                calls[m.group(1)] = c.group(1)
    for instr, target in calls.items():
        if target in first:
            names.setdefault(instr, first[target])
    return module, names


def stage_of(op: Op) -> str:
    """``round.<stage>``, ``traffic`` for the benchmark's own batch
    generator, or ``""``."""
    m = _STAGE.search(op.meta)
    if m:
        return m.group(0)
    return "traffic" if TRAFFIC in op.meta else ""


def is_kernel(op: Op, kernel: str) -> bool:
    """A Pallas kernel launch of ``kernel``: a custom call whose instruction
    is named after the kernel (``<kernel>`` or ``<kernel>.<n>``)."""
    return op.opcode == "custom-call" and (
        op.name == kernel or re.fullmatch(re.escape(kernel) + r"\.\d+",
                                          op.name) is not None)


def union(intervals) -> int:
    """Total length covered by ``(start, end)`` intervals."""
    return sum(e - s for s, e in merged(intervals))


def merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(ops) -> list:
    """Each op's duration less the durations of the ops nested directly in
    it (a loop and its body's ops), one device's ops at a time."""
    out = [o.end - o.start for o in ops]
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].device, ops[i].start, -ops[i].end))
    stack: list = []
    for i in order:
        o = ops[i]
        while stack and (ops[stack[-1]].device != o.device
                         or ops[stack[-1]].end <= o.start):
            stack.pop()
        if stack and o.end <= ops[stack[-1]].end:
            out[stack[-1]] -= o.end - o.start
        stack.append(i)
    return out


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def read_xplane(path: str, module: str = "", names: dict | None = None):
    """(device ops, host spans) of one trace file.  Ops of the program
    named ``module`` take their metadata from ``names``; other programs'
    ops carry the program's name."""
    from jax.profiler import ProfileData
    names = names or {}
    pd = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            runs = sorted((int(ev.start_ns), int(ev.end_ns),
                           ev.name.split("(")[0])
                          for ev in (lines[MODULES_LINE].events
                                     if MODULES_LINE in lines else ()))
            starts = [r[0] for r in runs]
            for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
                start, end = int(ev.start_ns), int(ev.end_ns)
                name, opcode = parse_event(ev.name)
                i = bisect.bisect_right(starts, start) - 1
                prog = runs[i][2] if i >= 0 and start < runs[i][1] else ""
                meta = names.get(name, "") if prog == module else prog
                ops.append(Op(plane.name, name, opcode, start, end, meta))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("perfbench."):
                        spans.append(Span(ev.name, int(ev.start_ns),
                                          int(ev.end_ns)))
    return ops, spans


class Summary:
    """One traced window, reduced."""

    def __init__(self, ops, spans, rounds: int):
        win = [s for s in spans if s.name == WINDOW_SPAN]
        if not win:
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        self.t0, self.t1 = win[0].start, win[0].end
        self.rounds = rounds
        self.spans = [s for s in spans if s.name != WINDOW_SPAN]
        self.ops = [o._replace(start=max(o.start, self.t0),
                               end=min(o.end, self.t1))
                    for o in ops if o.end > self.t0 and o.start < self.t1]
        self.self_ns = self_times(self.ops)
        self.devices = sorted({o.device for o in self.ops})

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        """Union of device-op intervals, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(union((o.start, o.end) for o in self.ops
                         if o.device == d)
                   for d in self.devices) / len(self.devices) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def stage_s(self) -> dict:
        """Device self seconds by stage (see the module docstring), summed
        over the window and averaged over the devices."""
        out = defaultdict(float)
        for o, t in zip(self.ops, self.self_ns):
            out[stage_of(o)] += t * 1e-9
        n = max(1, len(self.devices))
        return {k: v / n for k, v in out.items()}

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of ``kernel``'s launches, averaged over the
        devices."""
        t = sum(o.end - o.start for o in self.ops if is_kernel(o, kernel))
        return t * 1e-9 / max(1, len(self.devices))

    def top_ops(self, k: int = 10) -> list:
        """The ops with the most self time: ``name (opcode) [stage]``."""
        tot = defaultdict(int)
        for o, t in zip(self.ops, self.self_ns):
            st = stage_of(o)
            tot[f"{o.name} ({o.opcode})" + (f" [{st}]" if st else "")] += t
        n = max(1, len(self.devices))
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9 / n] for name, ns in best]

    def idle_gaps(self, k: int = 10) -> list:
        """The longest gaps between busy intervals of the first device,
        named after the host span covering most of each."""
        if not self.devices:
            return [["no device op", self.window_s]]
        busy = merged((o.start, o.end) for o in self.ops
                      if o.device == self.devices[0])
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
            cover = defaultdict(int)
            for sp in self.spans:
                ov = min(e, sp.end) - max(s, sp.start)
                if ov > 0:
                    cover[sp.name] += ov
            name = max(cover, key=cover.get) if cover else "host: no span"
            out.append([name, (e - s) * 1e-9])
        return out


def summarize(trace_dir: str, rounds: int, hlo_text: str = "") -> Summary:
    """The window of the trace under ``trace_dir``; ``hlo_text`` is the
    round program's compiled HLO, whose metadata names each op's stage."""
    module, names = op_names(hlo_text)
    ops, spans = read_xplane(find_xplane(trace_dir), module, names)
    return Summary(ops, spans, rounds)
