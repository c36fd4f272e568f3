"""From a JAX profiler trace (.xplane.pb) to device busy seconds, the
operations that took most time, and the longest idle gaps.

What a trace of this engine looks like (PERF.md section 3 has the
reading by hand): each chip is a plane `/device:TPU:<n>`; its line
`XLA Ops` holds one event per executed HLO operation and its line
`XLA Modules` one event per executed program, named `jit_<function>`
after the jitted Python function (`jit_filter_project`, ...). The host
is the plane `/host:CPU`, one line per thread; the benchmark's own
`TraceAnnotation`s (`bench:<template>:<n>`, one around each statement of
the slice) land there and tie the trace's clock to the host's.

The CPU backend has no device plane. In the rehearsal the XLA thread
pools of the host plane stand in for it, so that this code runs end to
end there; a number read that way is never a device number.
"""

import bisect

ANCHOR_PREFIX = "bench:"


def _device_lines(planes, platform):
    """{device: (op events, module events)} as (start_ns, end_ns, name)."""
    out = {}
    for plane in planes:
        if platform == "tpu" and plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
            out[plane.name] = (ops, mods)
        elif platform == "cpu" and plane.name == "/host:CPU":
            ops = []
            for line in plane.lines:
                if not line.name.startswith("tf_XLA"):
                    continue
                ops += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events
                        if e.duration_ns > 0 and
                        not e.name.startswith(("ThreadpoolListener",
                                               "end: ", "ThunkExecutor"))]
            out["/host:CPU (XLA threads)"] = (ops, [])
    return out


def anchors(planes):
    """The benchmark's own annotations: {name: (start_ns, end_ns)}."""
    out = {}
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ANCHOR_PREFIX):
                    out[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    return out


def union(intervals, lo, hi):
    """Merged intervals clipped to [lo, hi], in order."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals
                       if e > lo and s < hi):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def gaps(merged, lo, hi):
    """The complement of `merged` in [lo, hi] as (start, end)."""
    out, at = [], lo
    for s, e in merged:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def _top_ops(ops, mods, lo, hi, n):
    """Seconds by `<program>/<operation>`; the program is the XLA module
    whose event covers the operation's start."""
    mods = sorted(mods)
    starts = [m[0] for m in mods]
    total = {}
    for s, e, name in ops:
        if e <= lo or s >= hi:
            continue
        # XLA names a TPU operation by its whole HLO text: keep the
        # instruction's name, `%fusion.12`
        name = name.split(" = ")[0]
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and mods[i][1] >= s:
            name = f"{mods[i][2].split('(')[0]}/{name}"
        total[name] = total.get(name, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def reduce_planes(planes, platform: str, top: int = 10) -> dict:
    """busy_s (mean over devices), window_s, device_ops and gaps (ns, on
    the trace's clock) of the slice the anchors span; the whole trace
    where there is no anchor."""
    planes = list(planes)
    devices = _device_lines(planes, platform)
    marks = anchors(planes)
    every = [iv for ops, _ in devices.values() for iv in ops]
    if marks:
        lo = min(s for s, _ in marks.values())
        hi = max(e for _, e in marks.values())
    elif every:
        lo, hi = min(s for s, *_ in every), max(e for _, e, _ in every)
    else:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0,
                "device_ops": [], "gaps": [], "anchors": marks}
    busy, ops_all, mods_all, merged_all = [], [], [], []
    for ops, mods in devices.values():
        merged = union(ops, lo, hi)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        ops_all += ops
        mods_all += mods
        merged_all += merged
    idle = gaps(union(merged_all, lo, hi), lo, hi)
    return {"busy_s": sum(busy) / len(busy) if busy else 0.0,
            "window_s": (hi - lo) / 1e9,
            "devices": len(devices),
            "device_ops": [[n, s] for n, s in
                           _top_ops(ops_all, mods_all, lo, hi, top)],
            "gaps": sorted(idle, key=lambda g: g[0] - g[1])[:top],
            "anchors": marks}


def reduce_file(path: str, platform: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, platform, top)


def label_gaps(reduced: dict, statements: list) -> list:
    """[[label, seconds], ...] for the longest idle gaps. `statements`
    are the sliced statements with `anchor` (their annotation's name),
    `t_post_ns` (host clock) and `spans` (the coordinator's, host clock):
    a gap takes the name of the innermost span that covers its middle,
    `<template>:<span>`. Coarse: spans exist per statement, stage and
    split only."""
    marks = reduced["anchors"]
    out = []
    for g0, g1 in reduced["gaps"]:
        mid = (g0 + g1) // 2
        label = "between-statements"
        for st in statements:
            a = marks.get(st["anchor"])
            if a is None or not a[0] <= mid <= a[1]:
                continue
            label = f"{st['template']}:client"
            host_mid = st["t_post_ns"] + (mid - a[0])
            best = None
            for sp in st.get("spans") or ():
                s0 = int(sp.get("startTimeUnixNano", 0))
                s1 = s0 + int(float(sp.get("durationMs", 0.0)) * 1e6)
                if s0 <= host_mid <= s1 and \
                        (best is None or s1 - s0 < best[0]):
                    best = (s1 - s0, sp.get("name"))
            if best is not None:
                label = f"{st['template']}:{best[1]}"
            break
        out.append([label, (g1 - g0) / 1e9])
    return out
