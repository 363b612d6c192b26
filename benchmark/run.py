#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that sets the cell up from the seed, warms the cell's own
shapes (all of that is ``setup_s``), measures for ``--seconds``, compares
what the timed path produced with the plain reference, and prints as its
last line of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown`` (device time by the program's named scopes where the driver
has the compiled step's text; the whole table goes to standard error), and
last ``checks``, every number compared beside its limit
(also the last lines on standard error). No chip, or fewer than the cell
asks for: exit code 3 and no result.

``--cpu-dry-run`` runs the same code at the tiny sizes of the files'
``dry_run`` tables on the CPU (fake PJRT plug-in) and reports counts only;
``--control <name>`` puts a lower-precision reference or a planted fault in
the program's place and must come out not correct; ``--override k=v`` sets
one parameter of the traffic mix (rate sweeps). The driver passes none of
the three.
"""

import time

T_PROCESS = time.monotonic()

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


def load_cell(workload: str):
    """The cell's entries: from ``BENCHMARK.json``, or, for a cell that is
    not yet held to a bound, from ``benchmark/candidates.json`` (the same
    sections)."""
    for path in (os.path.join(ROOT, "BENCHMARK.json"),
                 os.path.join(HERE, "candidates.json")):
        with open(path) as f:
            manifest = json.load(f)
        cells = {w["name"]: w for w in manifest["workloads"]}
        if workload in cells:
            break
    else:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json or candidates.json")
    cell = cells[workload]
    (entry,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return manifest, cell, config, traffic


def metrics_of(manifest: dict, section: str, workload: str, e2e_names=None):
    """The entries of ``end_to_end`` or ``per_layer`` that this cell
    reports: those that list it under ``workloads``, and those with no such
    key (for a per-layer metric: where the cell reports its ``moves``)."""
    out = []
    for m in manifest[section]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e_names:
            out.append(m)
    return out


def read_layer_metric(name: str, run: dict):
    """``layer_metrics/<name>.json`` names a reader ``module.function``
    under ``readers/`` and its arguments."""
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    module, function = spec["reader"].rsplit(".", 1)
    reader = getattr(importlib.import_module("readers." + module), function)
    return reader(run, **spec.get("args", {}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-dry-run", action="store_true")
    ap.add_argument("--control", default=None)
    ap.add_argument("--override", action="append", default=[])
    args = ap.parse_args(argv)

    manifest, cell, config, traffic = load_cell(args.workload)
    for item in args.override:
        key, value = item.split("=", 1)
        traffic[key] = json.loads(value)
    if args.cpu_dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
    import harness

    ctx = harness.Context(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), dry=args.cpu_dry_run,
        control=args.control, t_process=T_PROCESS,
        spans=harness.SpanLog(annotate=bool(args.trace)))
    driver = importlib.import_module("drivers." + config["driver"])
    try:
        outcome = driver.run(ctx)
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 3

    e2e = metrics_of(manifest, "end_to_end", cell["name"])
    values = dict(outcome.end_to_end, setup_s=outcome.setup_s)
    metrics = {}
    device = dict(outcome.device)
    if not args.trace:
        for m in e2e:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        peak = None if ctx.dry else harness.load_peaks(device["kind"])
        run = {"ctx": ctx, "outcome": outcome, "trace": outcome.trace,
               "peak": peak}
        names = {m["name"] for m in e2e}
        for m in metrics_of(manifest, "per_layer", cell["name"], names):
            value = read_layer_metric(m["name"], run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if outcome.trace:
            device["busy_s"] = outcome.trace["busy_s"]
            device["window_s"] = outcome.trace["window_s"]
    correct = all(c["number"] <= c["limit"] for c in ctx.checks) and \
        bool(ctx.checks) and outcome.failed == 0
    result = {"correct": correct, "attempted": outcome.attempted,
              "failed": outcome.failed, "metrics": metrics, "device": device}
    if ctx.dry:
        result["metrics"] = {}
        result["dry_run"] = {"counts": outcome.counts,
                             "would_report": sorted(metrics)}
        tables = outcome.counters.get("op_scopes")
        if tables:      # a traced dry run: the scopes the compiled step names
            result["dry_run"]["scopes"] = sorted(
                {scope for t in tables.values() for scope, _ in t.values()})
    if args.trace and outcome.trace:
        result["breakdown"] = outcome.trace["breakdown"]
    result["checks"] = ctx.checks
    sys.stdout.flush()
    print(f"benchmark: seconds by phase: {json.dumps(ctx.laps)}",
          file=sys.stderr)
    if args.trace and outcome.trace and "scope_seconds" in outcome.trace:
        table = {k: outcome.trace[k] for k in (
            "module_runs", "module_seconds", "scope_seconds")}
        print("benchmark: device seconds by program, scope and phase: "
              f"{json.dumps(table)}", file=sys.stderr)
    for c in ctx.checks:
        print(f"benchmark: check {c['name']}: {c['number']!r} "
              f"(limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
