"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

* The traced window runs from the first to the last host span the
  benchmark wrote (``chipbench.*`` ``TraceAnnotation``s around every engine
  call and every turn of its own loop).
* Busy time is the union of the device's operation intervals inside the
  window, averaged over the chips that ran any; idle is the rest.
* Operation time is summed per operation of each program, over every
  chip.  On a TPU an operation's event name is its HLO text
  (``%copy.146 = bf16[...] copy(...)``), and the operations inside a loop
  are events nested in the loop's own.
* A kernel's time is its own events' and that of the operations of the
  same program that stage one of its operands into the chip's fast memory
  (memory space 1: an ``S(1)`` in the result's layout).  XLA may copy a
  weight from HBM into fast memory by a separate operation before the
  kernel runs, and the kernel alone would then read faster than HBM
  allows.  An operand the kernel reads from HBM counts nothing but the
  kernel: a slice, transpose, pad or convert that writes it back to HBM
  is the program's work, not the kernel's.
  For the breakdown each operation also gets a short label (program,
  opcode, result type, instance, and the parameter it reads) and its self
  time, the part of its interval no nested operation covers.
* Program executions (``XLA Modules``) are kept with their times, so a
  reader can sum one program's device time.
* Each idle gap is attributed to the host span that covers its midpoint.
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import re
from pathlib import Path

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "chipbench."


@dataclasses.dataclass(frozen=True)
class Interval:
    start: float   # ns
    end: float
    name: str

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Summary:
    window: tuple[float, float]
    n_devices: int
    busy_ns: float
    ops: dict[tuple, list]          # (program execution's name, HLO text) -> [ns, count]
    self_ns: dict[str, float]       # short label -> self time, all chips
    modules: list[Interval]
    gaps: list[Interval]            # idle gaps of the first chip, named by host span
    spans: list[Interval]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def kernel_ns(self, pattern: str) -> tuple[float, int]:
        """Time and count of the kernels whose HLO text matches, with the
        time of the operations that stage their operands into fast memory,
        each such operation counted once (and none that is itself such a
        kernel)."""

        rx = re.compile(pattern)
        by_name = {(prog, text.split(" = ", 1)[0]): (prog, text) for prog, text in self.ops}
        t, n, staging = 0.0, 0, set()
        for (prog, text), (ns, c) in self.ops.items():
            if rx.search(text):
                t += ns
                n += c
                staging.update(by_name.get((prog, o)) for o in operand_names(text))
        for key in staging - {None}:
            if in_fast_memory(key[1]) and not rx.search(key[1]):
                t += self.ops[key][0]
        return t, n

    def module_ns(self, pattern: str) -> tuple[float, int]:
        rx = re.compile(pattern)
        hits = [m.dur for m in self.modules if rx.search(m.name)]
        return float(sum(hits)), len(hits)


def load(path) -> "object":
    """ProfileData from an ``.xplane.pb`` file, gzipped or not."""

    from jax._src.profiler import ProfileData

    data = Path(path).read_bytes()
    if data[:2] == b"\x1f\x8b":
        data = gzip.decompress(data)
    return ProfileData.from_serialized_xspace(data)


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` pairs of ``intervals`` clipped to ``[lo, hi]``."""

    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covering(spans: list[Interval], starts: list[float], t: float) -> str:
    """The name of the host span around time ``t`` (the benchmark's spans
    follow one another on one thread and do not nest)."""

    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and spans[i].end >= t:
        return spans[i].name[len(SPAN_PREFIX):]
    return "outside the benchmark's spans"


def summarize(pd) -> Summary:
    spans: list[Interval] = []
    devices = []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Interval(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
        elif plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [Interval(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = [Interval(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
            if ops:
                devices.append((plane.name, ops, mods))
    if not spans:
        raise ValueError("the trace holds none of the benchmark's host spans")
    if not devices:
        raise ValueError("the trace holds no device operations")
    spans.sort(key=lambda s: s.start)
    lo, hi = spans[0].start, max(s.end for s in spans)
    devices.sort(key=lambda d: d[0])

    busy, opsum, selfsum, modules = [], {}, {}, []
    for _, ops, mods in devices:
        busy.append(sum(e - s for s, e in union(((o.start, o.end) for o in ops), lo, hi)))
        inside = [o for o in ops if lo <= o.start < hi]
        mods = sorted(mods, key=lambda m: m.start)
        for o in inside:
            acc = opsum.setdefault((program_of(mods, o.start), o.name), [0.0, 0])
            acc[0] += o.dur
            acc[1] += 1
        for o, t in self_times(inside):
            key = f"{program_of(mods, o.start).split('(')[0]}: {op_label(o.name)}"
            selfsum[key] = selfsum.get(key, 0.0) + t
        modules += [m for m in mods if lo <= m.start < hi]

    starts = [s.start for s in spans]
    merged = union(((o.start, o.end) for o in devices[0][1]), lo, hi)
    gaps, prev = [], lo
    for s, e in merged + [(hi, hi)]:
        if s > prev:
            gaps.append(Interval(prev, s, covering(spans, starts, (prev + s) / 2)))
        prev = max(prev, e)
    return Summary(window=(lo, hi), n_devices=len(devices), busy_ns=sum(busy) / len(busy),
                   ops=opsum, self_ns=selfsum, modules=sorted(modules, key=lambda m: m.start),
                   gaps=gaps, spans=spans)


def self_times(ops: list[Interval]):
    """``(op, self ns)`` for each operation: its duration less that of the
    operations nested directly inside it."""

    out, stack = [], []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1][0].end <= o.start:
            out.append(tuple(stack.pop()))
        if stack and o.end <= stack[-1][0].end:
            stack[-1][1] -= o.dur
        stack.append([o, o.dur])
    out += [tuple(x) for x in stack]
    return out


def program_of(modules: list[Interval], t: float) -> str:
    """The program (``jit_step_fn(<fingerprint>)``, one per compiled
    variant) whose execution covers time ``t``."""

    i = bisect.bisect_right([m.start for m in modules], t) - 1
    if i >= 0 and modules[i].end >= t:
        return modules[i].name
    return "no program"


def _balanced(s: str) -> int:
    """Length of the parenthesised group that ``s`` starts with."""

    depth = 0
    for i, ch in enumerate(s):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            return i + 1
    return len(s)


def parse_op(text: str) -> tuple[str, str, str, str]:
    """``(name, result type, opcode, operand list)`` of an HLO text; a
    tuple-typed result reads ``tuple``."""

    name, rest = text.split(" = ", 1)
    if rest.startswith("("):
        n = _balanced(rest)
        typ, rest = "tuple", rest[n:].lstrip()
    else:
        typ, _, rest = rest.partition(" ")
        typ = re.sub(r"\{[^}]*\}", "", typ)
    opcode, paren, args = rest.partition("(")
    args = (paren + args)[1:_balanced(paren + args) - 1] if paren else ""
    return name, typ, opcode, args


def in_fast_memory(text: str) -> bool:
    """Whether an HLO text's operation writes its result into memory space
    1, the chip's fast on-core memory (``S(1)`` in the result's layout)."""

    if " = " not in text:
        return False
    rest = text.split(" = ", 1)[1]
    typ = rest[:_balanced(rest)] if rest.startswith("(") else rest.partition(" ")[0]
    return "S(1)" in typ


def operand_names(text: str) -> list[str]:
    """The operands (``%pad.138``) an HLO text's operation reads."""

    if " = " not in text:
        return []
    return re.findall(r"%[\w.\-]+", parse_op(text)[3])


def op_label(text: str) -> str:
    """``copy bf16[24,8,1024,8,128] copy.146``-style label of an HLO text;
    a Pallas kernel's opcode reads ``pallas``, and a read of a parameter
    names it (``of lm_head``)."""

    if " = " not in text:
        return text
    name, typ, opcode, args = parse_op(text)
    if 'custom_call_target="tpu_custom_call"' in text:
        opcode = "pallas"
    label = f"{opcode} {typ} {name.lstrip('%')}"
    param = re.search(r"%params__([\w]+?)__\.\d+", args)
    if param:
        label += " of " + re.sub(r"_{2,}", ".", param.group(1))
    return label


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The device operations that took most (self) time and the longest
    idle gaps by what the host was doing, in seconds."""

    ops = sorted(summary.self_ns.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(summary.gaps, key=lambda g: -g.dur)[:top]
    return {"device_ops": [[n, ns * 1e-9] for n, ns in ops],
            "idle_gaps": [[g.name, g.dur * 1e-9] for g in gaps]}
