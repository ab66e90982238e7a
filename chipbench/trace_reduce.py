"""From a profiler trace (`.xplane.pb`) to numbers, with nothing but JAX.

Per device plane: the union of the intervals in which an operation ran
(busy), the span from the first operation's start to the last one's end
(window), self time per operation name, the executed programs (XLA modules)
and the idle gaps. Host annotations named `chipbench.*` give the offset
between the trace's clock and the harness's `perf_counter`, so that an idle
gap can be labelled by what the tick record says the host was doing.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "chipbench."


def union_length(intervals: List[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """Length of the union of `(start, end)` intervals, and the gaps inside it."""
    busy, gaps = 0.0, []
    cur_s: Optional[float] = None
    cur_e = 0.0
    for s, e in sorted(intervals):
        if cur_s is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_s is not None:
        busy += cur_e - cur_s
    return busy, gaps


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Self time per name for events that may nest on one line (a `while`
    holds its body's operations): an event's time minus its children's."""
    out: Dict[str, float] = {}
    stack: List[List[Any]] = []  # [end, name, self]
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            end, n, selft = stack.pop()
            out[n] = out.get(n, 0.0) + selft
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    while stack:
        end, n, selft = stack.pop()
        out[n] = out.get(n, 0.0) + selft
    return out


def base_name(name: str) -> str:
    """`fusion.123` -> `fusion`, `jit_f(987)` -> `jit_f`: instances of one
    operation read as one row."""
    name = name.split("(", 1)[0]
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def op_label(full: str) -> str:
    """`%fusion.3 = bf16[1024,1024]{1,0:T(8,128)} fusion(...)` -> `fusion.3 bf16[1024,1024]`: the device line
    names an operation by its whole HLO text."""
    head, sep, rest = full.partition(" = ")
    if not sep:
        return full.split("(", 1)[0][:80]
    shape = "(tuple)" if rest.startswith("(") else rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head.lstrip('%')} {shape}"[:80]


def op_kind(full: str) -> str:
    return base_name(full.partition(" = ")[0].lstrip("%"))


def read_planes(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, annotations, inventory = [], [], []
    for plane in pd.planes:
        inventory.append([plane.name, [[line.name, sum(1 for _ in line.events)] for line in plane.lines][:40]])
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, ev.name) for ev in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, ev.name) for ev in line.events]
            if ops or modules:
                devices.append({"name": plane.name, "ops": ops, "modules": modules})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        annotations.append((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, ev.name))
    return {"devices": devices, "annotations": sorted(annotations), "inventory": inventory}


def reduce_planes(planes: Dict[str, Any], chips: int = 1) -> Dict[str, Any]:
    per_device = []
    for dev in planes["devices"][:chips] if chips else planes["devices"]:
        intervals = [(s, e) for s, e, _ in dev["ops"]] or [(s, e) for s, e, _ in dev["modules"]]
        if not intervals:
            continue
        busy, gaps = union_length(intervals)
        first, last = min(s for s, _ in intervals), max(e for _, e in intervals)
        by_name: Dict[str, float] = {}
        by_kind: Dict[str, float] = {}
        custom: Dict[str, Dict[str, Any]] = {}
        counts: Dict[str, int] = {}
        for _s, _e, name in dev["ops"]:
            counts[name] = counts.get(name, 0) + 1
        for name, t in self_times(dev["ops"]).items():
            by_name[op_label(name)] = by_name.get(op_label(name), 0.0) + t
            by_kind[op_kind(name)] = by_kind.get(op_kind(name), 0.0) + t
            if op_kind(name) == "custom-call" or "custom_call_target" in name:
                custom[op_label(name)] = {"seconds": t, "count": counts[name], "text": name[:600]}
        modules: Dict[str, List[float]] = {}
        for s, e, name in dev["modules"]:
            modules.setdefault(base_name(name), []).append(e - s)
        per_device.append(
            {"name": dev["name"], "busy_s": busy, "window_s": last - first, "first": first, "last": last,
             "gaps": gaps, "ops_self_s": by_name, "ops_kind_s": by_kind, "custom_calls": custom, "modules": modules,
             "n_ops": len(dev["ops"])}
        )
    if not per_device:
        return {}
    n = len(per_device)
    return {
        "busy_s": sum(d["busy_s"] for d in per_device) / n,
        "window_s": sum(d["window_s"] for d in per_device) / n,
        "devices": per_device,
        "annotations": planes["annotations"],
        "inventory": planes["inventory"],
    }


def label_gaps(reduced: Dict[str, Any], flushes: List[Dict[str, Any]], flush_start: int) -> List[List[Any]]:
    """The ten longest idle gaps of the first device, each named after what
    the host was doing: `flush` (packing and the blocking submit), `env_loop`
    (player, env, staging), or `unlabelled` where the clocks cannot be tied."""
    dev = reduced["devices"][0]
    marks = [a for a in reduced["annotations"] if a[2] == ANNOTATION_PREFIX + "flush"]
    traced = flushes[flush_start : flush_start + len(marks)]
    offset = None
    if marks and len(traced) == len(marks):
        offset = marks[0][0] - traced[0]["t0"]  # trace clock minus perf_counter
    out = []
    for a, b in sorted(dev["gaps"], key=lambda g: g[0] - g[1])[:10]:
        label = "unlabelled"
        if offset is not None:
            in_flush = 0.0
            for f in traced:
                lo, hi = max(a, f["t0"] + offset), min(b, f["t1"] + offset)
                in_flush += max(0.0, hi - lo)
            label = "flush" if in_flush > 0.5 * (b - a) else "env_loop"
        out.append([label, b - a])
    return out


def breakdown(reduced: Dict[str, Any], flushes: List[Dict[str, Any]], flush_start: int) -> Dict[str, Any]:
    dev = reduced["devices"][0]
    ops = sorted(dev["ops_self_s"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": label_gaps(reduced, flushes, flush_start)}


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def reduce_dir(trace_dir: str, trace_info: Dict[str, Any], flushes: List[Dict[str, Any]], chips: int = 1) -> Optional[Dict[str, Any]]:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    reduced = reduce_planes(read_planes(path), chips)
    if not reduced:
        return None
    reduced["xplane_bytes"] = os.path.getsize(path)
    reduced["breakdown"] = breakdown(reduced, flushes, trace_info.get("flush_start", 0))
    reduced["grants"] = trace_info.get("grants_stop", 0) - trace_info.get("grants_start", 0)
    reduced["bursts"] = trace_info.get("flush_stop", 0) - trace_info.get("flush_start", 0)
    for dev in reduced["devices"]:  # keep what is printed and dumped small
        dev["gaps"] = sorted(dev["gaps"], key=lambda g: g[0] - g[1])[:50]
        dev["ops_self_s"] = dict(sorted(dev["ops_self_s"].items(), key=lambda kv: -kv[1])[:300])
    reduced["annotations"] = reduced["annotations"][:200]
    return reduced
