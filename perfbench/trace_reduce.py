"""From a profiler trace (.xplane.pb) to the numbers the per-layer metrics
read: per device the union of the intervals in which an operation ran, the
device time by operation name, and the idle gaps named by what the host
was doing. The arithmetic works on plain (name, start_ns, duration_ns)
lists, so the tests check it on a small recorded fixture."""
import re

OPS_LINE = "XLA Ops"          # the device plane's line of single operations
HOST_SPANS = ("perfbench.dispatch", "perfbench.wait")


def short_name(text):
    """The trace names an operation by its whole HLO line; keep the
    instruction's name, its result's type and the fusion kind."""
    head, _, rest = text.partition(" = ")
    kind = re.search(r"kind=(\w+)", rest)
    shape = re.match(r"\(?([A-Za-z0-9]+\[[0-9,]*\])", rest)
    parts = [head.lstrip("%"), shape.group(1) if shape else "",
             kind.group(1) if kind else ""]
    return " ".join(p for p in parts if p)[:96]


def busy_union(events, lo, hi):
    """Nanoseconds of [lo, hi) covered by at least one event, and the
    uncovered gaps as (start, end) pairs."""
    busy, gaps, edge = 0, [], lo
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        start, end = max(start, lo), min(start + dur, hi)
        if end <= edge:
            continue
        if start > edge:
            gaps.append((edge, start))
            edge = start
        busy += end - edge
        edge = end
    if hi > edge:
        gaps.append((edge, hi))
    return busy, gaps


def by_name(events, lo, hi):
    """Device nanoseconds by operation name inside [lo, hi)."""
    out = {}
    for name, start, dur in events:
        part = min(start + dur, hi) - max(start, lo)
        if part > 0:
            out[name] = out.get(name, 0) + part
    return out


def _host_label(gap, host):
    """The harness's host span that covers most of a gap."""
    best, label = 0, "no perfbench span"
    for name, start, dur in host:
        part = min(start + dur, gap[1]) - max(start, gap[0])
        if part > best:
            best, label = part, name
    return label


def reduce_events(devices, host, steps):
    """``devices``: one event list per device (its operations line);
    ``host``: the harness's own spans on the same clock; ``steps``: how
    many steps were traced. The window of each device runs from its first
    operation's start to its last one's end."""
    per_device = []
    for events in devices:
        lo = min(e[1] for e in events)
        hi = max(e[1] + e[2] for e in events)
        busy, gaps = busy_union(events, lo, hi)
        per_device.append({"window_ns": hi - lo, "busy_ns": busy,
                           "gaps": gaps, "by_name": by_name(events, lo, hi)})
    n = len(per_device)
    first = per_device[0]
    ops = sorted(first["by_name"].items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(first["gaps"], key=lambda g: g[0] - g[1])[:3]
    return {
        "steps": steps,
        "busy_s": sum(d["busy_ns"] for d in per_device) / n / 1e9,
        "window_s": sum(d["window_ns"] for d in per_device) / n / 1e9,
        "idle_share": sum(1 - d["busy_ns"] / d["window_ns"]
                          for d in per_device) / n,
        "by_name_s": {k: v / 1e9 for k, v in first["by_name"].items()},
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[_host_label(g, host), (g[1] - g[0]) / 1e9]
                          for g in gaps]}}


def read_xplane(path, chips, host_plane_only=False):
    """(devices, host) event lists of a .xplane.pb, read with JAX alone."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and not host_plane_only:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(plane.name.rsplit(":", 1)[1])] = [
                        (short_name(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(e.name, int(e.start_ns), int(e.duration_ns))
                          for e in line.events]
                host += [e for e in events if e[0] in HOST_SPANS]
                if host_plane_only and events:
                    # the rehearsal has no device plane: any host line
                    # stands in, so that the path is walked end to end
                    devices.setdefault(len(devices), events)
    ordered = [devices[k] for k in sorted(devices)][:chips]
    if len(ordered) < chips or not all(ordered):
        raise SystemExit("perfbench: the trace holds operations of %d "
                         "devices, the cell uses %d" % (len(ordered), chips))
    return ordered, host


def seconds_of(trace, *parts):
    """Device seconds (device 0) of the operations whose name holds one of
    ``parts``."""
    return sum(s for name, s in trace["by_name_s"].items()
               if any(p in name for p in parts))


def reduce_file(path, chips, steps, rehearse=False):
    devices, host = read_xplane(path, chips, host_plane_only=rehearse)
    return reduce_events(devices, host, steps)
