"""From a profiler trace to device busy time, program and kernel time.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a plain
structure (planes -> lines -> [name, start_ns, duration_ns]) with nothing
but JAX; ``reduce`` works on that structure alone, so the CPU tests check
it on a small recorded trace (``tests/benchmark/data``).

On a TPU each chip is a plane ``/device:TPU:<n>``. Its line ``XLA Ops``
holds one event per operation the core ran, in order and without overlap;
``XLA Modules`` holds one event per run of a compiled program. Host threads
are lines of the plane ``/host:CPU``; the program's spans and the
benchmark's own appear there as ``TraceAnnotation`` events.

busy        the union of the ``XLA Ops`` intervals of a chip, averaged
            over the chips that ran anything
programs    an ``XLA Modules`` event is one run of a compiled program. The
            engine's programs are all called ``jit__unknown(<hash>)`` today
            (it jits ``functools.partial`` objects), so a run is told apart
            by the operations inside it: ``contains``/``lacks`` are regular
            expressions on the full text of its operations
kernels     a Mosaic kernel is an operation whose text has
            ``custom_call_target="tpu_custom_call"``
window      from the first to the last event of the device lines and of
            the named host spans
idle gaps   the stretches of the window in which no operation ran, each
            put down to the host span that covers most of it
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_SPANS = re.compile(r"^(serving\.|bench\.)")
COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")


def find(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def load(path: str, keep_host=HOST_SPANS) -> Dict:
    """The trace as plain lists. Host events are kept only where their name
    matches ``keep_host``: the host planes hold millions of others."""
    from jax.profiler import ProfileData

    out = {"planes": []}
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if device or keep_host.match(e.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            out["planes"].append({"name": plane.name, "lines": lines})
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


SHORT_GAP_NS = 20_000


def _covering(host_sorted, starts, longest: int, a: int, b: int) -> str:
    """The host span an idle stretch [a, b) is put down to: the innermost
    of those that cover at least half of it, else the one that covers
    most. Stretches under 20 us are the seams between operations and are
    not looked up."""
    if b - a < SHORT_GAP_NS:
        return "(between operations)"
    inner, most = None, None
    for i in range(bisect.bisect_left(starts, a - longest),
                   bisect.bisect_left(starts, b)):
        name, s, d = host_sorted[i]
        o = min(b, s + d) - max(a, s)
        if o <= 0:
            continue
        if 2 * o >= b - a and (inner is None or d < inner[0]):
            inner = (d, name)
        if most is None or o > most[0]:
            most = (o, name)
    if inner:
        return inner[1]
    return most[1] if most else "(no host span)"


MOSAIC = r'custom_call_target="tpu_custom_call"'
WRAPPERS = ("while", "conditional", "call")


def reduce(tr: Dict) -> Dict:
    """Seconds, by the trace's own clock."""
    dev = [p for p in tr["planes"] if DEVICE.match(p["name"])]
    host = [e for p in tr["planes"] if not DEVICE.match(p["name"])
            for ln in p["lines"] for e in ln["events"]]
    ops_by_dev, mods = [], []
    for p in dev:
        for ln in p["lines"]:
            if ln["name"] == OPS_LINE and ln["events"]:
                ops_by_dev.append(ln["events"])
            elif ln["name"] == MODULES_LINE:
                mods.append(ln["events"])
    if not ops_by_dev:
        return {"busy_s": 0.0, "window_s": 0.0, "chips": 0, "op_events": [],
                "module_events": [], "idle_gaps": [], "device_ops": [],
                "collective_s": 0.0}
    edges = [(e[1], e[1] + e[2]) for ev in ops_by_dev for e in ev] + \
            [(e[1], e[1] + e[2]) for e in host]
    w0, w1 = min(a for a, _ in edges), max(b for _, b in edges)
    busy, collective = [], []
    ops: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    host_sorted = sorted(host, key=lambda e: e[1])
    starts = [e[1] for e in host_sorted]
    longest = max((e[2] for e in host), default=0)
    n = len(ops_by_dev)
    for ev in ops_by_dev:
        u = _union([(e[1], e[1] + e[2]) for e in ev])
        busy.append(sum(b - a for a, b in u))
        collective.append(sum(e[2] for e in ev
                              if COLLECTIVE.search(e[0].split(" = ")[0])))
        for e in ev:
            name = label(e[0])
            if name not in WRAPPERS:    # their bodies are events of their own
                ops[name] += e[2] / n
        idle = [(w0, u[0][0])] + [(u[i][1], u[i + 1][0])
                                  for i in range(len(u) - 1)] + [(u[-1][1], w1)]
        for a, b in idle:
            if b - a > 0:
                gaps[_covering(host_sorted, starts, longest, a, b)] += (
                    b - a) / n
    top = lambda d, k=10: [[name, v / 1e9] for name, v in sorted(
        d.items(), key=lambda x: -x[1])[:k]]
    return {"busy_s": sum(busy) / n / 1e9, "window_s": (w1 - w0) / 1e9,
            "chips": n, "collective_s": sum(collective) / n / 1e9,
            # one chip's programs and kernels stand for all: the chips of a
            # mesh run the same program
            "op_events": sorted(ops_by_dev[0], key=lambda e: e[1]),
            "module_events": sorted(mods[0], key=lambda e: e[1])
            if mods else [],
            "device_ops": top(ops), "idle_gaps": top(gaps)}


_PARAM = re.compile(r"%(params_[A-Za-z_]+?|pools_[A-Za-z_]+?)_*\.\d")


def label(text: str) -> str:
    """A short name for an operation's full text: its own name without the
    number, ``mosaic`` where it is a Mosaic kernel, and the first weight or
    pool it reads, which is what tells the fusions apart."""
    short = re.sub(r"\.\d+$", "", text.split(" = ")[0].lstrip("%"))
    if re.search(MOSAIC, text):
        short += ":mosaic"
    reads = _PARAM.search(text.partition(" = ")[2])
    return f"{short}[{reads.group(1)}]" if reads else short


def _matcher(pattern: Optional[str]):
    cache: Dict[str, bool] = {}
    rx = re.compile(pattern) if pattern else None

    def hit(text: str) -> bool:
        if rx is None:
            return False
        if text not in cache:
            cache[text] = bool(rx.search(text))
        return cache[text]
    return hit


def module_runs(red: Dict, pattern: str, contains: Optional[str] = None,
                lacks: Optional[str] = None) -> List[Tuple[int, int]]:
    """(start_ns, duration_ns) of every run of the programs whose name
    matches ``pattern`` and whose operations match ``contains`` and none of
    which matches ``lacks``."""
    name_rx = re.compile(pattern)
    has, bad = _matcher(contains), _matcher(lacks)
    ops = red["op_events"]
    op_starts = [e[1] for e in ops]
    out = []
    for name, s, d in red["module_events"]:
        if not name_rx.search(name):
            continue
        inside = ops[bisect.bisect_left(op_starts, s):
                     bisect.bisect_left(op_starts, s + d)]
        if contains and not any(has(e[0]) for e in inside):
            continue
        if lacks and any(bad(e[0]) for e in inside):
            continue
        out.append((s, d))
    return out


def op_seconds(red: Dict, pattern: str, lacks: Optional[str] = None,
               within: Optional[List[Tuple[int, int]]] = None) -> float:
    """Device seconds of the operations whose text matches ``pattern`` and
    not ``lacks``, optionally only inside the given program runs."""
    has, bad = _matcher(pattern), _matcher(lacks)
    total = 0
    if within is None:
        spans = [(0, float("inf"))]
    else:
        spans = within
    ops = red["op_events"]
    op_starts = [e[1] for e in ops]
    for s, d in spans:
        lo = bisect.bisect_left(op_starts, s)
        hi = len(ops) if d == float("inf") else bisect.bisect_left(
            op_starts, s + d)
        total += sum(e[2] for e in ops[lo:hi]
                     if has(e[0]) and not bad(e[0]))
    return total / 1e9
