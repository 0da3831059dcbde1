"""Per-layer metric readers, one file each, found by the metric's name.

``chipbench/metrics/<name>.py`` defines ``UNIT`` and ``read(ctx)``, which
returns the metric's value from the traced run's ``Context``, or ``None``
where it finds nothing to read (the harness then leaves the metric out of
the result line). A reader never returns 0 for a share of a roofline or a
peak it could not measure.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

DIR = Path(__file__).resolve().parent


@dataclass
class Context:
    cfg: Any                       # the program's ArchConfig, as run
    conf: Dict[str, Any]           # the configuration file
    mix: Any                       # the traffic mix
    window_s: float                # host seconds between the step boundaries
    steps: int                     # RL steps in the window
    c0: Dict[str, float]           # runtime counters at the window's start
    c1: Dict[str, float]           # ... and at its end
    work: Any                      # harness.WorkLog of the traced steps
    lowered: int                   # programs lowered inside the window
    groups: List[Tuple[int, List[int]]] = field(default_factory=list)
    device_kind: str = ""
    trace: Any = None              # chipbench.trace.Trace

    def delta(self, name: str) -> float:
        return self.c1[name] - self.c0[name]


def load(name: str):
    path = DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def names_for(bench: Dict[str, Any], workload: str) -> List[str]:
    return [m["name"] for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])]


def read_all(bench: Dict[str, Any], workload: str, ctx: Context
             ) -> Dict[str, Tuple[Optional[float], str]]:
    out = {}
    for name in names_for(bench, workload):
        mod = load(name)
        out[name] = (mod.read(ctx), mod.UNIT)
    return out
