"""From the profiler's ``.xplane.pb`` to device busy time, time by
operation, and idle gaps named by what the host was doing.

Read with nothing but JAX (``jax.profiler.ProfileData``). On a TPU the
planes ``/device:TPU:<n>`` carry the lines ``XLA Modules`` (one event per
program execution, named ``<program>(<fingerprint>)``) and ``XLA Ops`` (one
event per operation, named by its HLO text); ``/host:CPU`` carries one line
per thread, with the benchmark's own ``bench/<span>`` annotations among
its events. All times are nanoseconds on one clock.
"""

from __future__ import annotations

import bisect
import re

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OP = re.compile(r"^%([\w.\-]+) = \(?([a-z0-9]+\[[\d,]*\])")


def union_seconds(intervals) -> float:
    """The length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def short_op_name(hlo_text: str) -> str:
    """``%copy.3 = f32[131072,4096]{...} copy(...)`` -> ``copy.3_f32[131072,4096]``."""
    m = _OP.match(hlo_text)
    return f"{m.group(1)}_{m.group(2)}" if m else hlo_text[:60]


def program_of(module_name: str) -> str:
    """``brt_scatter_sub(1147...)`` -> ``brt_scatter_sub``."""
    return module_name.split("(", 1)[0]


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                     * 1e-9) for e in line.events]
    return []


def reduce_planes(devices: dict, host_spans: list, window=None) -> dict:
    """``devices``: chip index -> {"modules": [(name, s, e)], "ops":
    [(name, s, e)]}; ``host_spans``: [(name, s, e)] of the benchmark's
    annotations. The window is the profiler's, if given, else from the
    first to the last device event."""
    every = [ev for d in devices.values() for ev in d["modules"] + d["ops"]]
    if not every:
        raise SystemExit("benchmark: no operation ran on the device in the "
                         "traced window")
    w0, w1 = window or (min(e[1] for e in every), max(e[2] for e in every))
    busy = [union_seconds([(s, e) for _, s, e in d["ops"] or d["modules"]])
            for d in devices.values()]
    op_seconds, module_runs, module_seconds = {}, {}, {}
    first = devices[min(devices)]
    for d in devices.values():
        mods = sorted(d["modules"], key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, s, e in d["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            prog = program_of(mods[i][0]) if i >= 0 and s < mods[i][2] \
                else "no_module"
            key = f"{prog}:{short_op_name(name)}"
            op_seconds[key] = op_seconds.get(key, 0.0) + (e - s)
        for name, s, e in mods:
            prog = program_of(name)
            module_runs[prog] = module_runs.get(prog, 0) + 1
            module_seconds[prog] = module_seconds.get(prog, 0.0) + (e - s)
    # Idle gaps of the first chip, each named by the host span that covers
    # most of it.
    mods = sorted(first["modules"] or first["ops"], key=lambda m: m[1])
    gaps, edge = [], w0
    for _, s, e in mods:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if w1 > edge:
        gaps.append((edge, w1))
    by_span = {}
    spans = sorted(host_spans, key=lambda h: h[1])
    for g0, g1 in gaps:
        cover = {}
        for name, s, e in spans:
            if s >= g1:
                break
            lap = min(e, g1) - max(s, g0)
            if lap > 0:
                cover[name] = cover.get(name, 0.0) + lap
        name = max(cover, key=cover.get) if cover else "no_span"
        by_span[name] = by_span.get(name, 0.0) + (g1 - g0)
    module_gaps = [b[1] - a[2] for a, b in zip(mods, mods[1:])
                   if b[1] > a[2]]
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:10]  # noqa: E731
    return {
        "window_s": w1 - w0,
        "busy_s": sum(busy) / len(busy),
        "op_seconds": op_seconds,
        "module_runs": module_runs,
        "module_seconds": module_seconds,
        "module_gaps_s": module_gaps,
        "breakdown": {"device_ops": [[k, v] for k, v in top(op_seconds)],
                      "idle_gaps": [[k, v] for k, v in top(by_span)]},
    }


def reduce(path: str, chips: int, traced_s: float | None = None) -> dict:
    """``traced_s``: how long the profiler ran by the host's clock. The
    trace's own clock starts with the profiler, so the window is then
    [0, traced_s]; without it, from the first to the last device event."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host_spans = {}, []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m and int(m.group(1)) < chips:
            devices[int(m.group(1))] = {
                "modules": _events(plane, "XLA Modules"),
                "ops": _events(plane, "XLA Ops")}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench/"):
                        host_spans.append((e.name[6:], e.start_ns * 1e-9,
                                           (e.start_ns + e.duration_ns)
                                           * 1e-9))
    if not devices:
        raise SystemExit("benchmark: the trace holds no /device:TPU plane")
    window = None
    if traced_s is not None:
        last = max(e[2] for d in devices.values()
                   for e in d["modules"] + d["ops"])
        if last <= traced_s * 1.05:
            window = (0.0, max(traced_s, last))
    return reduce_planes(devices, host_spans, window)
