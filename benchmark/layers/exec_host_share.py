"""Worker tasks and executor: of the wall that ran under the fences of
`enable_profiling`, the share that was neither blocked on the device nor
in the compiler, over the window's statements, in %. Only as good as the
fences: they serialise the dispatch they time."""


def read(run):
    fenced = [s["fenced"] for s in run["statements"] if s.get("fenced")]
    wall = sum(f["wall_ms"] for f in fenced)
    if not wall:
        return None
    busy = sum(f["device_ms"] + f["compile_ms"] for f in fenced)
    return 100.0 * max(0.0, wall - busy) / wall
