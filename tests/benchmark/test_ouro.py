"""The Ouro-2.6B training cell's own tests: its configuration keeps the
catalog's keys, its manifest entries resolve, its work functions agree with
hand counts, its readers read a hand-made reduced trace (and nothing where
there is none), a dry run reports what the cell reports, and the comparison
that decides ``correct`` fails what it has to fail: the lower-precision
control and each planted fault, on the CPU at the dry-run size.
"""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
import work  # noqa: E402
import work_looped  # noqa: E402
from drivers import train_looped_step  # noqa: E402
from readers import device, looped, train  # noqa: E402

CONFIG_NAME = "train_ouro_2_6b_1chip"
CELL = CONFIG_NAME + ".loop4_2x4k"
with open(os.path.join(BENCH, "configs", CONFIG_NAME + ".json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
LAYER_METRICS = sorted(n + ".loop4_2x4k" for n in (
    "step_mfu", "device_idle_share", "step_gap_ms_p50", "attn_roofline",
    "exit_head_step_share", "exit_entropy"))

# ByteDance/Ouro-2.6B config.json, as the model-configs catalog holds it.
CATALOG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}


def test_the_config_keeps_every_published_width():
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG_NAME]
    assert entry["reduced"] == ["num_hidden_layers"]
    for key, value in CATALOG.items():
        if key == "num_hidden_layers":
            assert CONFIG["published"][key] == value and CONFIG[key] == 8
        else:
            assert CONFIG[key] == value, key
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG_NAME}.json"
    assert {"exit_beta", "objective", "layer", "biases", "init",
            "recomputation"} <= set(CONFIG["assumed"])
    assert CONFIG["exit_beta"] == 0.1 and CONFIG["reference_steps"] == 3
    assert "8 layers" in CONFIG["stands_for"]


def test_the_manifest_s_new_entries_resolve_to_files():
    (cell,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG_NAME, "loop4_2x4k", 1)
    assert len(cell["why"]) <= 200
    _, _, config, traffic = bench_run.load_cell(CELL)
    assert config["driver"] == "train_looped_step"
    assert (traffic["batch"], traffic["sequence"],
            traffic["trace_seconds"]) == (2, 4096, 8)
    for name in LAYER_METRICS:
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["workloads"] == [CELL] and spec["moves"] == "tokens_per_s"
    (tokens,) = [m for m in MANIFEST["end_to_end"]
                 if m["name"] == "tokens_per_s"]
    assert CELL in tokens["workloads"] and tokens["bound"] == 0.01


def test_the_cell_reports_tokens_per_s_and_six_layer_metrics():
    """The six it came with, among whatever later PRs appended (that each
    entry resolves to a file and a reader: ``test_benchmark.py``)."""
    e2e = {m["name"] for m in bench_run.metrics_of(MANIFEST, "end_to_end",
                                                   CELL)}
    assert e2e == {"tokens_per_s", "setup_s"}
    metrics = bench_run.metrics_of(MANIFEST, "per_layer", CELL, e2e)
    assert set(LAYER_METRICS) <= {m["name"] for m in metrics}
    assert all(m["workloads"] == [CELL] for m in metrics)


# -- work, against hand counts ---------------------------------------------------

def test_work_counts_against_hand_worked_numbers():
    m, b, t = CONFIG, 2, 4096
    # attention of one application: 16 heads, t(t+1)/2 pairs a sequence,
    # six products of 2 * pairs * 128
    pairs = b * 16 * t * (t + 1) // 2
    attn = work_looped.mha_attention(m, b, t)
    assert attn["flops"] == 6 * 2 * pairs * 128
    assert attn["bytes"] == 2 * b * t * 128 * 8 * 16
    assert work_looped.layer_applications(m) == 32
    # per token: the issue's 9.87 + 2.42 + 1.61 GFLOP
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert layer == 51_380_224
    step = work_looped.looped_train_step(m, b, t)
    tokens = b * t
    per_token = step["flops"] / tokens
    assert step["tokens"] == tokens
    assert 6 * layer * 32 / 1e9 == pytest.approx(9.865, abs=1e-3)
    assert 6 * 2048 * 49152 * 4 / 1e9 == pytest.approx(2.416, abs=1e-3)
    assert 32 * attn["flops"] / tokens / 1e9 == pytest.approx(1.611, abs=1e-3)
    assert per_token == pytest.approx(
        6 * layer * 32 + 6 * 2048 * 49152 * 4 + 32 * attn["flops"] / tokens)
    assert step["flops"] / 1e12 == pytest.approx(113.8, abs=0.05)
    # the heads' share of the work: 17% here, 3.4% at the published depth
    assert 2.416 / (per_token / 1e9) == pytest.approx(0.174, abs=2e-3)
    deep = work_looped.looped_train_step(dict(m, num_hidden_layers=48), b, t)
    assert 6 * 2048 * 49152 * 4 * tokens / deep["flops"] == pytest.approx(
        0.034, abs=1e-3)


# -- the readers, on a hand-made reduced trace --------------------------------------

def _run(trace, counters):
    return {"trace": trace, "peak": {"bf16_flops_per_s": 197e12,
                                     "hbm_bytes_per_s": 819e9},
            "outcome": types.SimpleNamespace(counters=counters)}


_COUNTERS = {"calls_in_trace": 5, "sizes": CONFIG, "batch": 2,
             "sequence": 4096, "step_flops": 113.8e12,
             "series": {"exit_entropy": [1.0, 0.9, 0.7]}}
_TRACE = {
    "window_s": 7.0, "busy_s": 6.9, "module_gaps_s": [2e-6, 4e-6],
    "module_seconds": {"jit_step": 6.8},
    "op_seconds": {
        "jit_step:attn_flash_fwd.8_bf16[2,16,4096,128]": 0.25,
        "jit_step:attn_flash_bwd.8_bf16[2,16,4096,128]": 0.40,
        "jit_step:fusion.652_bf16[2048,49152]": 0.20,
        "jit_step:convolution.7_f32[256,49152]": 0.30,
        "jit_step:fusion.9_f32[49152,2048]": 0.05,
        "jit_step:fusion.3_bf16[256,2048]": 0.11,       # the head's dx: no vocab
        "jit_step:fusion.4_f32[8,5632,2048]": 0.40,
        "jit_step:while.271_s32[]": 3.0,
        "jit_other:fusion.1_f32[64,49152]": 0.02,
    },
    # self seconds by the program's scope and phase (trace_reduce, given the
    # compiled step's text): the head and the loss over passes under
    # ``loop.head``, the optimizer under no scope outside the gradient
    "scope_seconds": {"jit_step": {
        "loop.head": {"fwd": 0.30, "remat": 0.25, "bwd": 0.31},
        "loop.head/loop.exit_loss": {"fwd": 0.02, "remat": 0.02, "bwd": 0.03},
        "loop.exit_gate": {"fwd": 0.01, "bwd": 0.02},
        "loop.layer.attn/attn.flash_fwd/attn_flash_fwd": {"fwd": 0.25},
        "loop.layer.mlp": {"fwd": 1.0, "remat": 1.0, "bwd": 2.0},
        "-": {"outside": 0.14, "bwd": 0.2, "none": 0.01}}}}


def test_readers_on_a_hand_made_trace():
    run = _run(_TRACE, _COUNTERS)
    one = work.roofline_seconds(work_looped.mha_attention(CONFIG, 2, 4096),
                                run["peak"])
    assert looped.attn_roofline(run, "attn_flash") == pytest.approx(
        100 * one * 32 * 5 / 0.65)
    head = ["loop.head", "loop.exit_loss", "loss.chunk"]
    assert train.scope_share_of_step(run, head, "jit_step") == pytest.approx(
        100 * (0.30 + 0.25 + 0.31 + 0.02 + 0.02 + 0.03) / 6.8)
    assert train.scope_share_of_step(
        run, ["opt.update", "-"], "jit_step", phases=["outside"]) == \
        pytest.approx(100 * 0.14 / 6.8)
    assert train.scope_share_of_step(run, ["-"], "jit_step") == \
        pytest.approx(100 * 0.35 / 6.8)
    assert train.stats_median(run, "exit_entropy") == 0.9
    assert device.step_mfu(run) == pytest.approx(
        100 * 113.8e12 * 5 / 7.0 / 197e12)
    for metric in MANIFEST["per_layer"]:
        if CELL in metric.get("workloads", ()):
            name = metric["name"]
            value = bench_run.read_layer_metric(name, dict(run, ctx=None))
            assert value is not None and value > 0, name
            if metric["unit"] == "%":
                assert value <= 100, name


def test_readers_return_none_where_there_is_nothing_to_read():
    no_trace = _run(None, _COUNTERS)
    assert looped.attn_roofline(no_trace, "attn_flash") is None
    assert train.scope_share_of_step(no_trace, ["loop.head"],
                                     "jit_step") is None
    # a program without such ops (the parent commit's), or a driver that
    # leaves no sizes
    other = _run(dict(_TRACE, op_seconds={"jit_step:fusion.1_f32[8]": 0.5}),
                 dict(_COUNTERS))
    assert looped.attn_roofline(other, "attn_flash") is None
    assert train.scope_share_of_step(other, ["gdn."], "jit_step") is None
    no_table = _run({k: v for k, v in _TRACE.items()
                     if k != "scope_seconds"}, _COUNTERS)
    assert train.scope_share_of_step(no_table, ["loop.head"],
                                     "jit_step") is None
    bare = _run(_TRACE, {"calls_in_trace": 2})
    assert looped.attn_roofline(bare, "attn_flash") is None
    assert train.stats_median(bare, "exit_entropy") is None


def test_the_gate_counts_as_one_leaf_against_its_own_norm():
    want = {"['embed']": 10.0, "['layers']['wq']": 8.0,
            "['exit_gate']['w']": 0.03, "['exit_gate']['b']": 0.0004}
    same = dict(want, **{"['exit_gate']['b']": 0.0008})       # a noisy bias
    assert train_looped_step.leaf_gap(same, want, "x") < 1e-3
    dead = dict(want, **{"['exit_gate']['w']": 0.0, "['exit_gate']['b']": 0.0})
    assert train_looped_step.leaf_gap(dead, want, "x") == pytest.approx(1.0)


# -- a dry run, and what the comparison has to fail ----------------------------------

def _dry(capsys, *extra):
    """One dry run of the cell in this process: (the result line, stderr)."""
    assert bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 7),
                           "--seconds", "0.5", "--cpu-dry-run", *extra]) == 0
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured.err


def test_dry_run_in_a_process_of_its_own_prints_the_last_line():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "0.5", "--cpu-dry-run"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    assert out["dry_run"]["would_report"] == ["setup_s", "tokens_per_s"]


def test_dry_run_reports_the_cell_s_metrics_and_checks(capsys):
    out, err = _dry(capsys, "--trace", "1")
    assert out["correct"] is True and out["failed"] == 0
    # on a CPU there is no device trace: of the cell's metrics the program's
    # own counter is read, and the trace's are named by the manifest
    assert out["dry_run"]["would_report"] == ["exit_entropy.loop4_2x4k"]
    # the table of scopes is built from the CPU-compiled step all the same
    assert {"loop.layer.attn", "loop.layer.mlp", "loop.pass_norm",
            "loop.exit_gate", "loop.head", "loop.head/loop.exit_loss",
            "-"} <= set(out["dry_run"]["scopes"])
    assert out["dry_run"]["counts"]["tokens_per_step"] == 2 * 64
    assert 0 < out["dry_run"]["counts"]["exit_entropy"] < 1.0987   # ln 3
    assert [c["name"] for c in out["checks"]] == [
        "loss_gap_step1", "pass_loss_gap", "grad_norm_gap", "delta_norm_gap",
        "exit_mass_gap", "last_loss_not_finite"]
    assert "not compared exit_entropy" in err


@pytest.mark.parametrize("control,by", [
    ("lowprec", "grad_norm_gap"), ("three_passes", "pass_loss_gap"),
    ("norm_last_only", "pass_loss_gap"), ("gate_detached", "grad_norm_gap"),
    ("no_entropy", "loss_gap_step1")])
def test_each_control_is_not_correct(control, by, capsys):
    out, _ = _dry(capsys, "--control", control)
    assert out["correct"] is False, out["checks"]
    failed = {c["name"] for c in out["checks"] if c["number"] > c["limit"]}
    assert by in failed, out["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "one_pass_fewer",
                                   "gate_frozen"])
def test_a_fault_in_the_program_is_not_correct(fault, monkeypatch, capsys):
    """The program broken underneath the driver: a step that leaves the
    state as it was, a loop that runs a pass too few, a gate that gets no
    gradient."""
    import dataclasses

    import jax

    from brpc_tpu.models import looped as model
    real = model.make_train_step
    if fault == "state_unchanged":
        def broken(cfg, optimizer):
            step = real(cfg, optimizer)
            return lambda p, s, t: (p, s, *step(p, s, t)[2:])
    elif fault == "one_pass_fewer":
        def broken(cfg, optimizer):
            return real(dataclasses.replace(
                cfg, total_ut_steps=cfg.total_ut_steps - 1), optimizer)
    else:
        real_log_probs = model.exit_log_probs
        monkeypatch.setattr(
            model, "exit_log_probs",
            lambda z: real_log_probs(jax.lax.stop_gradient(z)))
        broken = real
    monkeypatch.setattr(model, "make_train_step", broken)
    assert bench_run.main(["--workload", CELL, "--seed", "77", "--seconds",
                           "0.5", "--cpu-dry-run"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is False, out["checks"]
    failed = {c["name"] for c in out["checks"] if c["number"] > c["limit"]}
    assert failed >= {"state_unchanged": {"delta_norm_gap"},
                      "one_pass_fewer": {"pass_loss_gap", "exit_mass_gap"},
                      "gate_frozen": {"grad_norm_gap"}}[fault]
