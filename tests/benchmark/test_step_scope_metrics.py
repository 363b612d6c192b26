"""The scopes PR 37 gave the train steps, as the benchmark sees them: a
traced ``--cpu-dry-run`` of each train cell lists the cell's new names among
``dry_run.scopes`` (the table is built from the CPU-compiled step; there is
no device trace on a CPU, so no share of the step is reported), and each of
the three metric files that read the dense step's new scopes gives, through
``run.read_layer_metric``, the share a hand-made table holds and nothing
without a trace. Four more shares are queued for a ``benchmark`` PR (PERF.md
section 7 row 12: ``test_dsv3`` / ``test_ouro`` / ``test_qwen3_next`` hold
every entry of their cell to a positive reading on hand-made tables that
lack the new scopes); the reader is held here to the scopes they will
name."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
from readers import train  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

_SHARED = {"embed", "weights.cast", "opt.update"}
_CHUNKED = {"loss.chunk", "loss.chunk/loss.logits", "loss.chunk/loss.nll"}
# cell -> the scopes its compiled step names since PR 37 (on the CPU the
# attention is the dense form: ``attn.layout`` is on the kernels' path only,
# tests/test_ops.py compiles it for a v5e)
NEW_SCOPES = {
    "train_mistral7b_1chip.steady": _SHARED | {
        "llama.qkv", "llama.attn_out", "llama.mlp", "llama.head_loss"},
    "train_kanana2_30b_a3b_1chip.seq8k": _SHARED | _CHUNKED | {
        "mla.norm", "mla.rope", "dsv3.dense_mlp", "dsv3.glue"},
    "train_ouro_2_6b_1chip.loop4_2x4k": _SHARED | {
        "loop.head/loss.logits", "loop.head/loss.nll"},
    "train_qwen3_next_80b_a3b_1chip.lin3full1_8k": _SHARED | _CHUNKED | {
        "hybrid.glue"},
}

# metric -> (its cell, the share of the hand-made table below it must read)
NEW_METRICS = {
    "head_loss_step_share.steady":
        ("train_mistral7b_1chip.steady", 100 * 3.0 / 20.0),
    "mlp_step_share.steady":
        ("train_mistral7b_1chip.steady", 100 * 6.0 / 20.0),
    "attn_proj_step_share.steady":
        ("train_mistral7b_1chip.steady", 100 * 2.5 / 20.0),
}
# The shares queued for the other three cells: the scopes each will list,
# and the share of the hand-made table they read.
QUEUED = {
    "head_loss_step_share.seq8k": (["loss.chunk"], 100 * 1.75 / 20.0),
    "attn_layout_step_share.seq8k": (["mla.rope", "attn.layout"],
                                     100 * 2.25 / 20.0),
    "head_loss_step_share.lin3full1_8k": (["loss.chunk"], 100 * 1.75 / 20.0),
    "attn_layout_step_share.loop4_2x4k": (["attn.layout"],
                                          100 * 1.25 / 20.0),
}

# Self seconds by scope and phase, as ``trace_reduce.reduce_planes`` hands
# them on: 20 s of the step program in all.
_TABLE = {"jit_step": {
    "-": {"bwd": 1.0, "none": 0.5},
    "embed": {"fwd": 0.25, "bwd": 0.25},
    "weights.cast": {"fwd": 0.5},
    "opt.update": {"outside": 1.25},
    "llama.qkv": {"fwd": 0.5, "bwd": 1.0},
    "llama.attn_out": {"fwd": 0.25, "bwd": 0.75},
    "llama.mlp": {"fwd": 2.0, "bwd": 4.0},
    "llama.head_loss": {"fwd": 1.0, "bwd": 2.0},
    "mla.rope": {"fwd": 0.25, "remat": 0.25, "bwd": 0.5},
    "attn.layout": {"fwd": 0.25, "bwd": 0.5},
    "loop.layer.attn/attn.layout": {"remat": 0.5},
    "attn.flash_fwd/attn_flash_fwd": {"fwd": 0.5},
    "loss.chunk": {"fwd": 0.25},
    "loss.chunk/loss.logits": {"fwd": 0.25, "remat": 0.25, "bwd": 0.5},
    "loss.chunk/loss.nll": {"remat": 0.5},
    "loop.head/loss.logits": {"fwd": 0.25},     # loop.head's, not loss.chunk's
}}
assert sum(s for by_phase in _TABLE["jit_step"].values()
           for s in by_phase.values()) == 20.0


def _run(table):
    trace = {"module_seconds": {"jit_step": 20.0, "jit_slice": 0.5}}
    if table is not None:
        trace["scope_seconds"] = table
    return {"trace": trace}


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_a_new_share_reads_its_scopes_from_a_hand_made_table(metric):
    cell, want = NEW_METRICS[metric]
    (entry,) = (m for m in MANIFEST["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == [cell] and entry["moves"] == "tokens_per_s"
    assert (entry["source"], entry["better"], entry["unit"]) == (
        "device_trace", "lower", "%")
    assert bench_run.read_layer_metric(metric, _run(_TABLE)) == \
        pytest.approx(want)
    # nothing to read: a parent without the scopes, no table, no trace
    bare = {"jit_step": {"-": {"fwd": 16.0}, "opt.update": {"outside": 4.0}}}
    assert bench_run.read_layer_metric(metric, _run(bare)) is None
    assert bench_run.read_layer_metric(metric, _run(None)) is None
    assert bench_run.read_layer_metric(metric, {"trace": None}) is None


@pytest.mark.parametrize("metric", sorted(QUEUED))
def test_a_queued_share_s_scopes_read_what_the_table_holds(metric):
    scopes, want = QUEUED[metric]
    share = train.scope_share_of_step(_run(_TABLE), scopes, "jit_step")
    assert share == pytest.approx(want)
    assert train.scope_share_of_step(_run(None), scopes, "jit_step") is None


def test_the_manifest_holds_the_new_entries():
    # wherever later PRs append theirs
    assert set(NEW_METRICS) <= {m["name"] for m in MANIFEST["per_layer"]}


@pytest.mark.parametrize("cell", sorted(NEW_SCOPES))
def test_a_traced_dry_run_lists_the_cell_s_new_scopes(cell):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(2 ** 31 + 37), "--seconds", "1.5", "--cpu-dry-run",
         "--trace", "1"], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    scopes = set(out["dry_run"]["scopes"])
    assert NEW_SCOPES[cell] <= scopes, sorted(NEW_SCOPES[cell] - scopes)
    # no device trace on a CPU: none of the shares of the step is reported
    assert not [n for n in out["dry_run"]["would_report"]
                if n.endswith("_step_share." + cell.rsplit(".", 1)[1])]
