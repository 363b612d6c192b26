"""What every driver shares: the run's context, the look for a chip, the
benchmark's own spans, the traced window, and the numbers compared.

The harness is driven by data. ``BENCHMARK.json`` names a cell's
configuration and traffic mix; ``configs/<config>.json`` names the driver
module under ``drivers/``; ``traffic/<mix>.json`` holds the mix's
parameters; ``layer_metrics/<metric>.json`` names a reader function under
``readers/``. A later PR adds a cell, a configuration, a mix or a metric as
new files and manifest entries and edits none that is here.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Context:
    """One run: which cell, from which seed, for how long."""
    cell: dict                 # the manifest's workloads entry
    config: dict               # configs/<config>.json
    traffic: dict              # traffic/<mix>.json
    seed: int
    seconds: float
    trace: bool
    dry: bool                  # --cpu-dry-run: tiny sizes, counts only
    control: str | None        # a control or fault in the program's place
    t_process: float           # time.monotonic() when the process started
    spans: "SpanLog" = None
    checks: list = dataclasses.field(default_factory=list)
    laps: dict = dataclasses.field(default_factory=dict)
    _lap_t: float = 0.0

    def lap(self, name: str) -> None:
        """Seconds since the last lap (or the process's start), by name:
        what a run's set-up and its comparison are made of (stderr)."""
        now = time.monotonic()
        self.laps[name] = round(now - (self._lap_t or self.t_process), 3)
        self._lap_t = now

    def size(self, key: str):
        """A size of the configuration: the file's own value, or under
        --cpu-dry-run the tiny one from its ``dry_run`` table."""
        if self.dry and key in self.config.get("dry_run", {}):
            return self.config["dry_run"][key]
        return self.config[key]

    def mix(self, key: str, default=None):
        if self.dry and key in self.traffic.get("dry_run", {}):
            return self.traffic["dry_run"][key]
        return self.traffic.get(key, default)

    def check(self, name: str, number: float, limit=None) -> None:
        """One number compared, beside its limit: correct iff number <=
        limit. The limit is the configuration's own (``limits``; measured
        on the chip, PERF.md), under --cpu-dry-run the tiny size's."""
        if limit is None:
            limit = self.size("limits")[name]
        self.checks.append({"name": name, "number": float(number),
                            "limit": float(limit)})


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to ``run.py``."""
    end_to_end: dict           # metric name -> value (setup_s added by run.py)
    attempted: int
    failed: int
    setup_s: float
    device: dict               # platform, kind, count, memory_peak_bytes
    counters: dict = dataclasses.field(default_factory=dict)
    trace: dict | None = None  # trace_reduce.reduce()'s result, traced runs
    counts: dict = dataclasses.field(default_factory=dict)   # dry-run output


class SpanLog:
    """The benchmark's own spans: (name, start, end, bytes, thread) on
    ``time.monotonic``, kept in memory. Traced runs also write each span
    into the profiler's trace, so idle gaps can be named by host span."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.rows = []
        self._mu = threading.Lock()

    def add(self, name, t0, t1, nbytes=0):
        with self._mu:
            self.rows.append((name, t0, t1, nbytes, threading.get_ident()))

    def span(self, name, nbytes=0):
        return _Span(self, name, nbytes)

    def named(self, name):
        return [r for r in self.rows if r[0] == name]


class _Span:
    def __init__(self, log, name, nbytes):
        self.log, self.name, self.nbytes = log, name, nbytes
        self.ann = None

    def __enter__(self):
        if self.log.annotate:
            import jax.profiler
            self.ann = jax.profiler.TraceAnnotation("bench/" + self.name)
            self.ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.log.add(self.name, self.t0, t1, self.nbytes)
        return False


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by the nearest-rank rule on the sorted
    values: the smallest value with at least q% of all at or below it."""
    s = sorted(values)
    if not s:
        raise ValueError("no values")
    rank = max(1, -(-len(s) * q // 100))
    return float(s[int(rank) - 1])


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise SystemExit(f"benchmark: no peaks for device kind "
                         f"{device_kind!r} in peaks.json")
    return peaks[device_kind]


def jax_devices(chips: int, dry: bool):
    """The devices JAX reports, refused unless they are what the cell is
    for. Turns the program's persistent compilation cache on."""
    import jax

    sys.path.insert(0, ROOT)
    from brpc_tpu import compile_cache

    devices = jax.devices()
    want = "cpu" if dry else "tpu"
    if devices[0].platform != want or len(devices) < chips:
        raise NoChip(f"benchmark: JAX reports {len(devices)} x "
                     f"{devices[0].platform!r}; the cell needs {chips} x "
                     f"{want!r}")
    compile_cache.enable()
    return devices


def device_report(devices, chips: int) -> dict:
    """The device as JAX reports it; the peak on the fullest chip used."""
    peak = 0
    for d in devices[:chips]:
        peak = max(peak, int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class TracedWindow:
    """The profiler around (part of) the window of a ``--trace 1`` run. The
    trace is reduced at once and its directory removed: a run leaves no
    trace on the disk."""

    def __init__(self, on: bool):
        self.on = on
        self.t0 = self.t1 = None

    def start(self):
        if not self.on:
            return
        import jax.profiler
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # no event per Python call
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        self.t0 = time.monotonic()

    def stop(self):
        if not self.on or self.t1 is not None:
            return
        import jax.profiler
        self.t1 = time.monotonic()
        jax.profiler.stop_trace()

    def reduce(self, chips: int, scopes=None):
        if not self.on:
            return None
        import trace_reduce
        self.stop()
        try:
            files = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise SystemExit("benchmark: the profiler wrote no trace")
            return trace_reduce.reduce(files[0], chips, self.t1 - self.t0,
                                       scopes)
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)


def back_to_back(dispatch, seconds: float, in_flight: int,
                 window: TracedWindow, traced_s: float, spans: SpanLog):
    """Calls ``dispatch()`` back to back for ``seconds``, keeping at most
    ``in_flight`` of its results pending (JAX returns before the device is
    done, so an unbounded loop would only measure the enqueue), and closes
    the last with ``block_until_ready``: all the calls over all the time.
    A traced window is stopped, drained, after ``traced_s``. Returns
    (calls, calls inside the traced window, elapsed seconds, last result)."""
    import jax

    t0 = time.monotonic()
    pending, calls, calls_in_trace = [], 0, None
    while time.monotonic() - t0 < seconds:
        with spans.span("dispatch"):
            pending.append(dispatch())
        calls += 1
        if len(pending) > in_flight:
            with spans.span("wait"):
                pending.pop(0).block_until_ready()
        if window.on and calls_in_trace is None and \
                time.monotonic() - t0 >= traced_s:
            jax.block_until_ready(pending)
            calls_in_trace = calls
            window.stop()
    jax.block_until_ready(pending)
    elapsed = time.monotonic() - t0
    if window.on and calls_in_trace is None:
        calls_in_trace = calls
        window.stop()
    return calls, calls_in_trace or 0, elapsed, pending[-1]
