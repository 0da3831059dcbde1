"""From a profiler trace to device busy time, kernel time and the breakdown.

Two stages. ``extract`` reads the ``.xplane.pb`` that ``jax.profiler``
wrote into plain event lists: device operations (per chip), the device's
module-level events (one per run of a compiled program), and the host
spans the harness put around the runtime's calls into each layer.
``Trace`` reduces those lists; it is what the metric readers use, and what
``chipbench/tests`` check on a small recorded trace.

The traced window runs from the first host span's start to the last one's
end. Busy time is the union of the device operations' intervals inside it,
averaged over the chips used; idle gaps are the stretches in it with no
operation on the device, each labelled by the innermost host span open at
its start.
"""
from __future__ import annotations

import glob
import json
import re
from collections import defaultdict
from dataclasses import asdict, dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)
SPAN_NAMES = ("rollout", "coordinator", "trainer", "refill", "reward")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Events:
    ops: Dict[str, List[Event]] = field(default_factory=dict)      # per chip
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # per chip
    spans: List[Event] = field(default_factory=list)

    def to_json(self) -> str:
        return json.dumps(asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "Events":
        raw = json.loads(text)
        return cls(
            ops={k: [tuple(e) for e in v] for k, v in raw["ops"].items()},
            modules={k: [tuple(e) for e in v] for k, v in raw["modules"].items()},
            spans=[tuple(e) for e in raw["spans"]],
        )


def extract(directory: Path) -> Events:
    """Plain event lists from the one ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    files = glob.glob(str(directory / "plugins" / "profile" / "*" / "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {directory}, found {files}")
    data = ProfileData.from_file(files[0])
    ev = Events()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ev.ops[plane.name] = [
                        (e.name, e.start_ns, e.duration_ns) for e in line.events
                    ]
                elif line.name == MODULES_LINE:
                    ev.modules[plane.name] = [
                        (e.name, e.start_ns, e.duration_ns) for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ev.spans.extend(
                    (e.name, e.start_ns, e.duration_ns)
                    for e in line.events if e.name in SPAN_NAMES
                )
    return ev


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


_INSTANCE = re.compile(r"\.\d+$")
# operations that hold others: their body's operations are traced too
CONTAINERS = ("while", "conditional", "call")


def op_kind(name: str) -> str:
    """An operation's kind: the device trace names each operation by its
    HLO text (``%fusion.12 = f32[8,128]{1,0} fusion(...)``); the kind is the
    instruction's name without ``%`` and its instance number (``fusion``).
    """
    head = name.split(" = ", 1)[0].lstrip("%")
    return _INSTANCE.sub("", head) or head


class Trace:
    def __init__(self, events: Events):
        self.events = events
        if not events.spans:
            raise ValueError("the trace holds no host span of the harness")
        self.t0 = min(s for _, s, _ in events.spans)
        self.t1 = max(s + d for _, s, d in events.spans)
        self.chips = sorted(events.ops)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _busy(self, chip: str) -> List[Tuple[float, float]]:
        ops = self.events.ops.get(chip, [])
        return _union(_clip([(s, s + d) for _, s, d in ops], self.t0, self.t1))

    @property
    def busy_s(self) -> float:
        """Seconds with an operation on the device, averaged over chips."""
        if not self.chips:
            return 0.0
        total = sum(e - s for c in self.chips for s, e in self._busy(c))
        return total / len(self.chips) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, match: Callable[[str], bool]) -> float:
        """Summed device time of the operations whose name matches,
        averaged over chips."""
        if not self.chips:
            return 0.0
        total = sum(
            d for c in self.chips for n, s, d in self.events.ops[c]
            if match(n) and self.t0 <= s < self.t1
        )
        return total / len(self.chips) / 1e9

    def module_runs(self, match: Callable[[str], bool]) -> Tuple[int, float]:
        """(runs, summed device seconds) of the programs whose module name
        matches, averaged over chips."""
        if not self.chips:
            return 0, 0.0
        runs, total = 0, 0.0
        for c in self.chips:
            for n, s, d in self.events.modules.get(c, []):
                if match(n) and self.t0 <= s < self.t1:
                    runs += 1
                    total += d
        return runs // len(self.chips), total / len(self.chips) / 1e9

    def gaps(self, chip: Optional[str] = None) -> List[Tuple[float, float]]:
        chip = chip or (self.chips[0] if self.chips else None)
        busy = self._busy(chip) if chip else []
        out, t = [], self.t0
        for s, e in busy:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.t1:
            out.append((t, self.t1))
        return out

    def span_at(self, t: float) -> str:
        """The innermost harness span open at ``t`` (host time), or
        ``between spans``."""
        best, width = "between spans", float("inf")
        for n, s, d in self.events.spans:
            if s <= t < s + d and d < width:
                best, width = n, d
        return best

    def breakdown(self, top: int = 10) -> Dict[str, List[List]]:
        chip = self.chips[0] if self.chips else None
        kinds: Dict[str, float] = defaultdict(float)
        for n, s, d in (self.events.ops.get(chip, []) if chip else []):
            kind = op_kind(n)
            if self.t0 <= s < self.t1 and kind not in CONTAINERS:
                kinds[kind] += d / 1e9
        ops = sorted(kinds.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps(chip), key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[self.span_at(s), (e - s) / 1e9] for s, e in gaps],
        }


def read(directory: Path) -> Trace:
    return Trace(extract(directory))
