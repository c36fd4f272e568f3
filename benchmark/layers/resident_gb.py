"""Device: bytes the executor keeps on the device across statements
(`residentBytes` on the `execute` span) at the end of the window's last
statement, in GB (1e9 bytes). A program without the counter gives
nothing to read."""

from layers import _spans


def read(run):
    if not run["statements"]:
        return None
    last = max(run["statements"], key=lambda s: s["t_done"])
    for sp in _spans.named(last.get("spans"), "execute"):
        kept = (sp.get("attributes") or {}).get("residentBytes")
        if kept is not None:
            return kept / 1e9
    return None
