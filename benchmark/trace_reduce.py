"""From the profiler's ``.xplane.pb`` to device busy time, time by
operation, and idle gaps named by what the host was doing.

Read with nothing but JAX (``jax.profiler.ProfileData``). On a TPU the
planes ``/device:TPU:<n>`` carry the lines ``XLA Modules`` (one event per
program execution, named ``<program>(<fingerprint>)``) and ``XLA Ops`` (one
event per operation, named by its HLO text); ``/host:CPU`` carries one line
per thread, with the benchmark's own ``bench/<span>`` annotations among
its events. All times are nanoseconds on one clock.

Device time is also put down to the scope the program wrote an operation
under (``jax.named_scope``): the compiled program's text names every
instruction's ``op_name`` path, ``op_scopes`` turns that into a table, and
``reduce_planes``, given the table, sums each operation's SELF time (a
``while`` contains its body's operations on the ``XLA Ops`` line) by scope.
"""

from __future__ import annotations

import bisect
import re

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OP = re.compile(r"^%([\w.\-]+) = \(?([a-z0-9]+\[[\d,]*\])")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
# What JAX itself puts into an op_name path (an einsum's spec among it); the
# rest the program wrote.
_TRANSFORMS = ("jvp", "transpose", "vmap")
_JAX_OWN = re.compile(
    r"^(jit|pjit|jvp|transpose|vmap|while|body|cond|branch_\w+|closed_call|"
    r"checkpoint|rematted_computation|custom_jvp\w*|custom_vjp\w*)(\(.*)?$"
    r"|.*->")
NO_SCOPE = "-"


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


def _path(op_name: str) -> list:
    """The components of an ``op_name`` path: split at ``/`` outside
    parentheses, and a transform's argument -- ``jvp(loop.head)``: the name
    stack the transform was applied under -- spliced in after its name."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(op_name + "/"):
        depth += (ch == "(") - (ch == ")")
        if ch == "/" and depth <= 0:
            parts.append(op_name[start:i])
            start = i + 1
    out = []
    for part in parts:
        head, _, inner = part.partition("(")
        if head in _TRANSFORMS and inner.endswith(")"):
            out += [head] + _path(inner[:-1])
        elif part:
            out.append(part)
    return out


def scope_of(op_name: str) -> tuple:
    """An instruction's ``op_name`` path -> (scope, phase). The scope is the
    path less its last component (the primitive) and JAX's own components,
    what the program wrote left in order and joined by ``/``; ``-`` where
    the program wrote nothing. A ``cond`` repeats the whole path from
    ``jit(...)`` on inside its branch: the scope is read after the last
    repeat. The phase says which pass of the step the instruction belongs
    to: ``remat`` (recomputed in the backward pass), ``bwd``, ``fwd``, or
    ``outside`` the differentiated computation (the optimizer, and what of
    the forward pass depends on no parameter: index arithmetic, tables)."""
    parts = _path(op_name.split(";", 1)[0])         # XLA joins merged names
    if not parts:
        return NO_SCOPE, "outside"
    phase = ("remat" if "rematted_computation" in parts else
             "bwd" if "transpose" in parts else
             "fwd" if "jvp" in parts else "outside")
    last = max(i for i, p in enumerate(parts) if p == parts[0])
    own = [p for p in parts[last:-1] if not _JAX_OWN.match(p)]
    return "/".join(own) or NO_SCOPE, phase


def op_scopes(hlo_text: str) -> dict:
    """The compiled program's text (``compiled.as_text()``) ->
    {program: {instruction name: [scope, phase]}}, for every instruction of
    the module. A fusion whose own line has no metadata (XLA:TPU leaves it
    off some, and nests fusions) takes its fused computation's root's, else
    the most frequent among that computation's instructions; an instruction
    with none at all (a copy the compiler put in) is ``["-", "none"]``."""
    program, inside = "", None
    table, calls, roots, members = {}, {}, {}, {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            name, op = m.group(2), _OP_NAME.search(line)
            table[name] = list(scope_of(op.group(1))) if op else None
            callee = None if op else _CALLS.search(line)
            if callee:
                calls[name] = callee.group(1)
            members.setdefault(inside, []).append(name)
            if m.group(1):
                roots[inside] = name
            continue
        m = _COMPUTATION.match(line)
        if m or line.startswith("}"):
            inside = m.group(1) if m else None
            continue
        m = _MODULE.match(line)
        if m:
            program = m.group(1)

    def placed(name, seen=()):
        if table.get(name) is not None or name not in calls:
            return table.get(name)
        callee = calls[name]
        if callee in seen:
            return None
        got = placed(roots.get(callee), seen + (callee,))
        if got is None:
            found = [tuple(g) for g in (placed(n, seen + (callee,))
                                        for n in members.get(callee, ())) if g]
            got = list(max(set(found), key=found.count)) if found else None
        return got

    return {program: {name: placed(name) or [NO_SCOPE, "none"]
                      for name in table}}


def program_of(module_name: str) -> str:
    """``brt_scatter_sub(1147...)`` -> ``brt_scatter_sub``."""
    return module_name.split("(", 1)[0]


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                     * 1e-9) for e in line.events]
    return []


def self_seconds(events) -> list:
    """Each event's SELF time, in the order given: every instant of the
    union of the (name, start, end) events goes to the event that started
    last among those running then, so a ``while`` keeps what its body's
    operations leave of it, and the self times add up to the union."""
    out = [0.0] * len(events)
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    running, t = [], 0.0        # indices, the last started last; the clock

    def run_to(to):
        nonlocal t
        while running and t < to:
            inner = running[-1]
            end = min(events[inner][2], to)
            if end > t:
                out[inner] += end - t
                t = end
            if events[inner][2] <= to:
                running.pop()
        t = to

    for i in order:
        run_to(events[i][1])
        running.append(i)
    run_to(float("inf"))
    return out


def _device_ops(op_seconds, op_self_seconds, placed):
    """What the breakdown lists: the operations of a program with a scope
    table (``placed``: key -> [scope, phase]) by ``<program>:<scope>``
    groups of self seconds, one the program wrote no scope around singly as
    ``<program>:-/<op>``; a program without a table by operation, as it
    was."""
    rows = {}
    for key, seconds in op_seconds.items():
        if key not in placed:
            rows[key] = seconds
            continue
        program, op = key.split(":", 1)
        scope = placed[key][0]
        name = f"{program}:{scope}" + (f"/{op}" if scope == NO_SCOPE else "")
        rows[name] = rows.get(name, 0.0) + op_self_seconds[key]
    return rows


def reduce_planes(devices: dict, host_spans: list, window=None,
                  scopes=None) -> dict:
    """``devices``: chip index -> {"modules": [(name, s, e)], "ops":
    [(name, s, e)]}; ``host_spans``: [(name, s, e)] of the benchmark's
    annotations. The window is the profiler's, if given, else from the
    first to the last device event. ``scopes``: ``op_scopes``' table of
    the traced programs, if the driver has one: the result then also holds
    ``scope_seconds`` ({program: {scope: {phase: self seconds}}}) and the
    breakdown lists device time by scope."""
    every = [ev for d in devices.values() for ev in d["modules"] + d["ops"]]
    if not every:
        raise SystemExit("benchmark: no operation ran on the device in the "
                         "traced window")
    w0, w1 = window or (min(e[1] for e in every), max(e[2] for e in every))
    busy = [union_seconds([(s, e) for _, s, e in d["ops"] or d["modules"]])
            for d in devices.values()]
    op_seconds, module_runs, module_seconds = {}, {}, {}
    op_self_seconds, placed = {}, {}
    first = devices[min(devices)]
    for d in devices.values():
        mods = sorted(d["modules"], key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for (name, s, e), own in zip(d["ops"], self_seconds(d["ops"])):
            i = bisect.bisect_right(starts, s) - 1
            prog = program_of(mods[i][0]) if i >= 0 and s < mods[i][2] \
                else "no_module"
            key = f"{prog}:{short_op_name(name)}"
            op_seconds[key] = op_seconds.get(key, 0.0) + (e - s)
            op_self_seconds[key] = op_self_seconds.get(key, 0.0) + own
            if scopes and prog in scopes and key not in placed:
                m = _OP.match(name)
                placed[key] = scopes[prog].get(m.group(1) if m else name,
                                               [NO_SCOPE, "none"])
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
    out = {
        "window_s": w1 - w0,
        "busy_s": sum(busy) / len(busy),
        "op_seconds": op_seconds,
        "op_self_seconds": op_self_seconds,
        "module_runs": module_runs,
        "module_seconds": module_seconds,
        "module_gaps_s": module_gaps,
        "breakdown": {"device_ops": [[k, v] for k, v in top(op_seconds)],
                      "idle_gaps": [[k, v] for k, v in top(by_span)]},
    }
    if scopes:
        by_scope = {}
        for key, (scope, phase) in placed.items():
            phases = by_scope.setdefault(key.split(":", 1)[0], {}).setdefault(
                scope, {})
            phases[phase] = phases.get(phase, 0.0) + op_self_seconds[key]
        out["scope_seconds"] = by_scope
        out["breakdown"]["device_ops"] = [[k, v] for k, v in top(
            _device_ops(op_seconds, op_self_seconds, placed))]
    return out


def reduce(path: str, chips: int, traced_s: float | None = None,
           scopes=None) -> dict:
    """``traced_s``: how long the profiler ran by the host's clock. The
    trace's own clock starts with the profiler, so the window is then
    [0, traced_s]; without it, from the first to the last device event.
    ``scopes``: as ``reduce_planes`` takes it."""
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
    return reduce_planes(devices, host_spans, window, scopes)
