"""Reduction of a JAX profiler trace to what the per-layer readers read.

A run traced with ``--trace 1`` writes one ``.xplane.pb``.  It holds one
plane per TPU chip (``/device:TPU:<n>``) whose ``XLA Ops`` line has an
event per device operation, and the host plane (``/host:CPU``) whose
``python`` line carries the benchmark's own ``TraceAnnotation`` spans
(names starting ``bench.``).  Both are on the trace's one clock.

The traced window is the host span ``bench.window``.  Busy time of a chip
is the union of its operations' intervals inside the window; ``busy_s``
is its mean over the chips.  An idle gap is a stretch of the window in
which a chip runs no operation; it is named by the benchmark span that
covers most of it (the window itself when no narrower span does).
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)
WINDOW = "bench.window"


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]            # chip -> device operations
    spans: List[Event]                     # benchmark host spans
    window: Tuple[float, float]            # ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)

    def _in_window(self, chip: int) -> List[Tuple[float, float]]:
        w0, w1 = self.window
        return [(max(s, w0), min(e, w1)) for _, s, e in self.ops[chip]
                if e > w0 and s < w1]

    def busy_intervals(self, chip: int) -> List[Tuple[float, float]]:
        merged: List[List[float]] = []
        for s, e in sorted(self._in_window(chip)):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return [(s, e) for s, e in merged]

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, mean over the chips."""
        if not self.ops:
            return 0.0
        return sum(sum(e - s for s, e in self.busy_intervals(c))
                   for c in self.chips) * 1e-9 / len(self.ops)

    def op_seconds(self, match: Callable[[str], bool]) -> float:
        """Device seconds of the operations whose name ``match``es,
        inside the window, mean over the chips."""
        if not self.ops:
            return 0.0
        w0, w1 = self.window
        tot = 0.0
        for c in self.chips:
            tot += sum(min(e, w1) - max(s, w0) for n, s, e in self.ops[c]
                       if match(n) and e > w0 and s < w1)
        return tot * 1e-9 / len(self.ops)

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Every idle stretch of every chip in the window, longest first,
        named by the host span that covers most of it."""
        gaps = []
        for c in self.chips:
            t = self.window[0]
            for s, e in self.busy_intervals(c) + [(self.window[1],) * 2]:
                if s > t:
                    gaps.append((self._label(t, s), (s - t) * 1e-9))
                t = max(t, e)
        return sorted(gaps, key=lambda g: -g[1])

    def _label(self, s: float, e: float) -> str:
        best, cover = WINDOW, 0.0
        for n, a, b in self.spans:
            if n == WINDOW:
                continue
            ov = min(b, e) - max(a, s)
            if ov > cover:
                best, cover = n, ov
        return best

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (by name, summed
        over the window, mean over chips) and the longest idle gaps."""
        per = collections.Counter()
        w0, w1 = self.window
        for c in self.chips:
            for n, s, e in self.ops[c]:
                if e > w0 and s < w1:
                    per[short(n)] += ((min(e, w1) - max(s, w0)) * 1e-9
                                      / len(self.ops))
        return {"device_ops": [[n, v] for n, v in per.most_common(top)],
                "idle_gaps": [[n, v] for n, v in self.idle_gaps()[:top]]}


COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")
_OPCODE = re.compile(r"[\]\})] ([a-z][a-z0-9_\-]*)\(")


def opcode(text: str) -> str:
    """The HLO opcode of a device operation.  On a TPU the event's name
    is the instruction's HLO text: ``%name = <type> <opcode>(...)``."""
    m = _OPCODE.search(text)
    return m.group(1) if m else text.split(".", 1)[0].lstrip("%")


def short(text: str) -> str:
    """``%name opcode type`` of a device operation, for the breakdown."""
    head, _, rest = text.partition(" = ")
    op = opcode(text)
    typ = rest.split(" " + op + "(", 1)[0] if rest else ""
    return f"{head} {op} {typ[:60]}".strip()


def is_collective(text: str) -> bool:
    return opcode(text).startswith(COLLECTIVES)


def is_mm_kernel(text: str) -> bool:
    """The MM-aggregation kernel: a Mosaic custom call issued from the
    aggregation engine's jitted launch (``_agg_nd_impl``)."""
    return "tpu_custom_call" in text and "_agg" in text.partition(" = ")[0]


def _chip_index(plane_name: str) -> Optional[int]:
    head = "/device:TPU:"
    if not plane_name.startswith(head):
        return None
    tail = plane_name[len(head):]
    return int(tail) if tail.isdigit() else None


def from_profile(pd, n_chips: Optional[int] = None) -> Trace:
    """Build a ``Trace`` from ``jax.profiler.ProfileData``."""
    ops: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        chip = _chip_index(plane.name)
        if chip is not None:
            if n_chips is not None and chip >= n_chips:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip] = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                 for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    return Trace(ops=ops, spans=spans, window=windows[-1])


def trace_file(trace_dir) -> str:
    found = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir, n_chips: Optional[int] = None) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(trace_file(trace_dir)), n_chips)


@dataclasses.dataclass
class ReadContext:
    """What a per-layer reader gets: the reduced trace and the run's
    facts (counters, harness-timed spans, work counts)."""

    trace: Trace
    facts: dict
