"""From the profiler's trace to numbers.

Two stages, so that the second can be checked on a small recorded trace
(``tests/data``): ``load_xplane`` flattens an ``.xplane.pb`` into plain
events ``{"plane", "line", "name", "start_ns", "dur_ns"}``; ``reduce``
turns events into: the busy union of each device (seconds in which an
operation ran), device time by program (XLA module) and by operation, the
collectives' time and the part of it in which no other operation ran on
that device, and the idle gaps of the first device attributed to the
benchmark's own host spans (``bench.upload``, ``bench.dispatch``,
``bench.wait``, ``bench.readback``).  The window is the host's: the span
``bench.window`` that the harness puts around the measured window, so that
a stall before the first launch or after the last counts as idle.
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load_xplane(path: str) -> list:
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        device = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name not in (_OPS_LINE, _MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                events.append({
                    "plane": plane.name, "line": line.name, "name": ev.name,
                    "start_ns": float(ev.start_ns), "dur_ns": float(ev.duration_ns),
                })
    return events


def _union(intervals: list) -> list:
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def _length(intervals: list) -> float:
    return sum(hi - lo for lo, hi in intervals)


def _subtract(a: list, b: list) -> list:
    """The parts of the merged intervals ``a`` that no interval of the
    merged ``b`` covers."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append([cur, hi])
    return out


def _self_times(timed: list) -> list:
    """[(interval, name)] -> [(interval, name, self ns, is a leaf)]: an
    operation that covers others (a `while` and its body) keeps as its own
    only the time that no operation inside it takes."""
    order = sorted(timed, key=lambda t: (t[0][0], -t[0][1]))
    out, stack = [], []  # stack of indices into out
    for iv, name in order:
        while stack and out[stack[-1]][0][1] <= iv[0]:
            stack.pop()
        if stack and iv[1] <= out[stack[-1]][0][1]:
            parent = out[stack[-1]]
            parent[2] -= iv[1] - iv[0]
            parent[3] = False
        out.append([iv, name, iv[1] - iv[0], True])
        stack.append(len(out) - 1)
    return [tuple(o) for o in out]


def stable_name(name: str) -> str:
    """A program's name without the run's identifier
    (``jit__weighted_bcd_fit(123)`` -> ``jit__weighted_bcd_fit``), and an
    operation's name with its result type from the HLO line that the trace
    gives as its name (``%fusion.7 = f32[8,8]{1,0:T(8,128)} fusion(...`` ->
    ``fusion.7 f32[8,8]``)."""
    if " =" in name:
        op, _, rest = name.partition(" =")
        shape = rest.strip().split("{")[0].split(" ")[0].strip()
        return (op.strip().lstrip("%") + " " + shape).strip()
    return name.split("(")[0].strip().lstrip("%")


def reduce(events: list, window_ns=None) -> dict:
    """``window_ns`` = (start, end) cuts every event to the traced window;
    None takes the host's ``bench.window`` span, and only where the events
    hold none the span of the device events."""
    devices: dict = {}
    spans = []
    for ev in events:
        m = _DEVICE_PLANE.match(ev["plane"])
        if m:
            devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})[
                "ops" if ev["line"] == _OPS_LINE else "modules"
            ].append(ev)
        elif ev["name"] == WINDOW_SPAN:
            if window_ns is None:
                window_ns = (ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
        elif ev["name"].startswith(SPAN_PREFIX):
            spans.append(ev)
    if not devices:
        return {"devices": 0}
    if window_ns is None:
        starts = [e["start_ns"] for d in devices.values() for e in d["ops"] + d["modules"]]
        ends = [e["start_ns"] + e["dur_ns"] for d in devices.values()
                for e in d["ops"] + d["modules"]]
        window_ns = (min(starts), max(ends))
    w_lo, w_hi = window_ns

    def clip(ev):
        lo, hi = max(ev["start_ns"], w_lo), min(ev["start_ns"] + ev["dur_ns"], w_hi)
        return (lo, hi) if hi > lo else None

    busy, exposed, collective = {}, {}, {}
    ops_s, modules_s, module_runs = {}, {}, {}
    gaps_first = []
    for dev, d in sorted(devices.items()):
        timed = d["ops"] or d["modules"]
        all_iv, coll_iv, other_iv = [], [], []
        clipped = [(clip(ev), ev["name"]) for ev in timed]
        for iv, name, self_ns, leaf in _self_times([c for c in clipped if c[0]]):
            all_iv.append(iv)
            if leaf:  # a parent (a `while`) is no compute of its own
                (coll_iv if _COLLECTIVE.match(name.lstrip("%")) else other_iv).append(iv)
            if d["ops"]:
                key = stable_name(name)
                ops_s[key] = ops_s.get(key, 0.0) + self_ns / 1e9
        for ev in d["modules"]:
            iv = clip(ev)
            if iv is None:
                continue
            key = stable_name(ev["name"])
            modules_s[key] = modules_s.get(key, 0.0) + (iv[1] - iv[0]) / 1e9
            if dev == min(devices):
                module_runs[key] = module_runs.get(key, 0) + 1
        merged = _union(all_iv)
        busy[dev] = _length(merged) / 1e9
        coll = _union(coll_iv)
        collective[dev] = _length(coll) / 1e9
        exposed[dev] = _length(_subtract(coll, _union(other_iv))) / 1e9
        if dev == min(devices):
            gaps_first = _subtract([[w_lo, w_hi]], merged)
    n_dev = len(devices)
    by_span: dict = {}
    span_iv = [(s["name"][len(SPAN_PREFIX):], s["start_ns"], s["start_ns"] + s["dur_ns"])
               for s in spans]
    for lo, hi in gaps_first:
        covered = 0.0
        for name, s_lo, s_hi in span_iv:
            part = min(hi, s_hi) - max(lo, s_lo)
            if part > 0:
                by_span[name] = by_span.get(name, 0.0) + part / 1e9
                covered += part
        if hi - lo - covered > 0:
            by_span["outside_spans"] = by_span.get("outside_spans", 0.0) + (hi - lo - covered) / 1e9
    top = lambda d: sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])[:10]
    return {
        "devices": n_dev,
        "window_s": (w_hi - w_lo) / 1e9,
        "busy_s": sum(busy.values()) / n_dev,
        "busy_s_by_device": busy,
        "collective_s_fullest": max(collective.values()),
        "exposed_collective_s_fullest": max(exposed.values()),
        "module_s": {k: v / n_dev for k, v in modules_s.items()},
        "module_runs": module_runs,
        "op_s": {k: v / n_dev for k, v in ops_s.items()},
        "device_ops": top({k: v / n_dev for k, v in ops_s.items()}),
        "idle_gaps": top(by_span),
    }


def idle_pct(reduction):
    """1 - busy union over the traced window, averaged over the chips; None
    where no device plane was traced."""
    if not reduction or not reduction.get("devices"):
        return None
    return 100.0 * (1.0 - reduction["busy_s"] / reduction["window_s"])
