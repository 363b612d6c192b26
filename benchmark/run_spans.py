#!/usr/bin/env python3
"""``run.py`` with the entries of ``span_metrics.json`` appended to the
``per_layer`` of the manifest that holds the cell: same arguments, same
last line.

    python3 benchmark/run_spans.py --workload <candidate cell> --seed <n> --seconds <s> --trace 1

The per-layer metrics that read the program's own span trees
(``readers/program.py``) belong in ``candidates.json`` beside their cells;
the PR that brought them changed the program and so could add files here
but not edit that one. Until a benchmark PR moves the entries there and
deletes this script, a traced run of a candidate cell through it reports
them with the cell's others."""

import json
import os
import sys

import run


def load_cell(workload: str, _load=run.load_cell):
    manifest, cell, config, traffic = _load(workload)
    with open(os.path.join(run.HERE, "span_metrics.json")) as f:
        extra = json.load(f)["per_layer"]
    manifest = dict(manifest, per_layer=manifest["per_layer"] + extra)
    return manifest, cell, config, traffic


if __name__ == "__main__":
    run.load_cell = load_cell
    sys.exit(run.main())
