"""The Qwen3-Next-80B-A3B training cell's own tests: its configuration keeps
the catalog's keys, its manifest entries resolve, its work functions agree
with hand counts, its readers read a hand-made reduced trace (and nothing
where there is none), and the comparison that decides ``correct`` fails what
it has to fail: the lower-precision control and each planted fault, on the
CPU at the dry-run size.
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
import work_dsv3  # noqa: E402
import work_gdn  # noqa: E402
from readers import device, hybrid, train  # noqa: E402

CONFIG_NAME = "train_qwen3_next_80b_a3b_1chip"
CELL = CONFIG_NAME + ".lin3full1_8k"
with open(os.path.join(BENCH, "configs", CONFIG_NAME + ".json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

# Qwen/Qwen3-Next-80B-A3B-Instruct config.json, as the model-configs catalog
# holds it.
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
NINE = ("step_mfu", "device_idle_share", "step_gap_ms_p50", "gdn_roofline",
        "gdn_step_share", "gated_attn_roofline", "moe_gmm_roofline",
        "moe_rows_step_share", "expert_load_max_over_mean")
CONTROLS = ("lowprec", "half_batch", "no_decay", "no_delta", "no_out_gate",
            "drop_tenth", "no_shared_gate")


def test_the_config_keeps_the_catalog_s_keys():
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG_NAME]
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in CATALOG.items():
        if key in reduced:
            assert CONFIG["published"][key] == value, key
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (4, 32, 18992)
    assert CONFIG["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert CONFIG["router_experts"] == CATALOG["num_experts"]
    assert CONFIG["num_hidden_layers"] == CATALOG["full_attention_interval"]
    assert entry["source"] == CONFIG["source"]
    assert set(CONFIG["reduced_why"]) == reduced
    for text in ("stands_for", "limits_why"):
        assert CONFIG[text]


def test_manifest_entries_resolve():
    (cell,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    manifest, cell, config, traffic = bench_run.load_cell(CELL)
    assert config["driver"] == "train_hybrid_step"
    assert os.path.exists(os.path.join(BENCH, "drivers",
                                       config["driver"] + ".py"))
    assert (traffic["batch"], traffic["sequence"],
            traffic["trace_seconds"]) == (1, 8192, 5)
    assert traffic["dry_run"]["sequence"] == 64
    (tokens,) = [m for m in MANIFEST["end_to_end"]
                 if m["name"] == "tokens_per_s"]
    assert CELL in tokens["workloads"]


def test_the_cell_reports_tokens_per_s_and_nine_layer_metrics():
    e2e = {m["name"] for m in bench_run.metrics_of(MANIFEST, "end_to_end",
                                                   CELL)}
    assert e2e == {"tokens_per_s", "setup_s"}
    metrics = bench_run.metrics_of(MANIFEST, "per_layer", CELL, e2e)
    # the nine it came with, among whatever later PRs appended
    assert {n + ".lin3full1_8k" for n in NINE} <= {m["name"] for m in metrics}
    for m in metrics:
        assert m["moves"] == "tokens_per_s" and m["workloads"] == [CELL]
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert (spec["layer"], spec["unit"]) == (m["layer"], m["unit"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


# -- work, against hand counts ---------------------------------------------------

def test_work_counts_against_hand_worked_numbers():
    m = dict(CONFIG, n_routed_experts=CONFIG["num_experts"])
    t = 8192
    # the recurrence: 32 value heads, 3 products of 2 * 128 * 128 a token
    # forward and twice that backward: 9 x 8.59 GFLOP a layer
    rule = work_gdn.gated_delta_rule(m, 1, t)
    assert rule["flops"] == 9 * 2 * t * 32 * 128 * 128
    assert 77.2e9 < rule["flops"] < 77.4e9
    # q, k of 16 heads, v, o of 32, in bf16; g, beta in float32; and as much
    # again for the gradients and dO
    assert rule["bytes"] == 2 * t * (2 * (2 * 2048 + 2 * 4096) + 4 * 64)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.roofline_seconds(rule, peak) == rule["bytes"] / 819e9
    # attention: 16 query heads, t(t+1)/2 pairs, 6 products over 256
    pairs = 16 * t * (t + 1) // 2
    attn = work_gdn.gqa_attention(m, 1, t)
    assert attn["flops"] == 6 * 2 * pairs * 256
    assert attn["bytes"] == 2 * t * 256 * (4 * 16 + 4 * 2)
    assert work.roofline_seconds(attn, peak) == attn["flops"] / 197e12
    assert work_gdn.layer_counts(m) == (3, 1)
    assert work_gdn.layer_counts(dict(m, num_hidden_layers=48)) == (36, 12)
    # the grouped products: the kanana cell's function at this cell's sizes
    routed = work_dsv3.routed_experts(m, 5120)
    assert routed["flops"] == 9 * 2 * 5120 * 2048 * 512
    assert routed["bytes"] == 9 * 2 * (5120 * (2048 + 512) + 32 * 2048 * 512)
    # per token and forward pass, the issue's count
    linear = (2 * (2048 * 12288 + 2048 * 64 + 4096 * 2048) + 2 * 4 * 8192
              + 3 * 2 * 32 * 128 * 128)
    full = (2 * (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048)
            + 2 * 2 * 256 * 16 * (t + 1) // 2)
    moe = 2 * (2048 * 512 + 3 * 2048 * 512 + 2048)
    head = 2 * 2048 * 18992
    rows = [5000, 5100, 5200, 5300]
    fwd = t * (3 * linear + full + 4 * moe + head) + sum(
        2 * 3 * r * 2048 * 512 for r in rows)
    step = work_gdn.hybrid_train_step(m, 1, t, rows)
    assert step == {"flops": 3 * fwd, "tokens": t}
    assert 11.0e12 < step["flops"] < 11.6e12          # 11.3 TFLOP a step


# -- the readers, on a hand-made reduced trace --------------------------------------

def _run(trace, counters):
    return {"trace": trace, "peak": {"bf16_flops_per_s": 197e12,
                                     "hbm_bytes_per_s": 819e9},
            "outcome": types.SimpleNamespace(counters=counters)}


_SIZES = dict(CONFIG, n_routed_experts=CONFIG["num_experts"])
_COUNTERS = {"calls_in_trace": 2, "sizes": _SIZES, "batch": 1,
             "sequence": 8192, "step_flops": 11.3e12,
             "routed_rows": [[5120] * 4, [5000] * 4, [1] * 4],
             "series": {"expert_load_max_over_mean": [1.2, 1.1, 1.4]}}
_TRACE = {
    "window_s": 1.0, "busy_s": 0.99, "module_gaps_s": [1e-5, 3e-5],
    "module_seconds": {"jit_step": 0.6},
    "op_seconds": {
        "jit_step:gdn_chunk_fwd.1_bf16[1,32,8192,128]": 0.05,
        "jit_step:gdn_chunk_bwd.2_f32[1,32,8192,128]": 0.07,
        "jit_step:attn_flash_fwd.3_bf16[1,16,8192,256]": 0.010,
        "jit_step:attn_flash_bwd.4_bf16[1,16,8192,256]": 0.020,
        "jit_step:moe_gmm_fwd.5_bf16[90112,512]": 0.010,
        "jit_step:moe_gmm_dlhs.6_bf16[90112,2048]": 0.006,
        "jit_step:moe_gmm_drhs.7_bf16[32,2048,512]": 0.004,
        "jit_step:moe_rows_gather.8_bf16[90112,16,128]": 0.012,
        "jit_step:moe_rows_combine.9_bf16[8192,2048]": 0.006,
        "jit_step:while.3_s32__": 0.5,
        "jit_other:gdn_chunk_fwd.1_bf16[8,8]": 0.002,
    },
    # self seconds by the program's scope and phase (trace_reduce, given the
    # compiled step's text)
    "scope_seconds": {"jit_step": {
        "gdn.conv": {"fwd": 0.02, "remat": 0.02, "bwd": 0.04},
        "gdn.rule/gdn.chunk_fwd/gdn_chunk_fwd": {"fwd": 0.05},
        "moe.experts/moe_gmm_fwd": {"fwd": 0.01},
        "moe.router": {"fwd": 0.02, "remat": 0.01, "bwd": 0.01},
        "-": {"outside": 0.05, "fwd": 0.02, "bwd": 0.04, "none": 0.01}}}}


def test_readers_on_a_hand_made_trace():
    run = _run(_TRACE, _COUNTERS)
    peak = run["peak"]
    rule = work.roofline_seconds(work_gdn.gated_delta_rule(_SIZES, 1, 8192),
                                 peak)
    assert hybrid.kernel_roofline(run, "gdn_", "gated_delta_rule",
                                  "linear") == \
        pytest.approx(100 * rule * 2 * 3 / 0.122)
    attn = work.roofline_seconds(work_gdn.gqa_attention(_SIZES, 1, 8192),
                                 peak)
    assert hybrid.kernel_roofline(run, "attn_flash", "gqa_attention",
                                  "full") == \
        pytest.approx(100 * attn * 2 * 1 / 0.030)
    least = 4 * sum(work.roofline_seconds(
        work_dsv3.routed_experts(_SIZES, r), peak) for r in (5120, 5000))
    assert train.kernel_roofline(run, "moe_gmm", "routed_experts") == \
        pytest.approx(100 * least / 0.020)
    assert train.op_share_of_step(run, "gdn_", "jit_step") == \
        pytest.approx(100 * 0.122 / 0.6)
    assert train.op_share_of_step(run, "moe_rows", "jit_step") == \
        pytest.approx(100 * 0.018 / 0.6)
    assert train.scope_share_of_step(run, ["gdn.conv"], "jit_step") == \
        pytest.approx(100 * 0.08 / 0.6)
    assert train.scope_share_of_step(run, ["moe."], "jit_step") == \
        pytest.approx(100 * 0.05 / 0.6)
    assert train.scope_share_of_step(
        run, ["opt.update", "-"], "jit_step", phases=["outside"]) == \
        pytest.approx(100 * 0.05 / 0.6)
    assert train.scope_share_of_step(run, ["-"], "jit_step") == \
        pytest.approx(100 * 0.12 / 0.6)
    assert device.step_mfu(run) == pytest.approx(
        100 * 11.3e12 * 2 / 1.0 / 197e12)
    for metric in MANIFEST["per_layer"]:
        if CELL in metric.get("workloads", ()):
            value = bench_run.read_layer_metric(metric["name"], dict(
                run, ctx=None))
            assert value is not None and value > 0, metric["name"]
            if metric["unit"] == "%":
                assert value <= 100, metric["name"]


def test_readers_return_none_where_there_is_nothing_to_read():
    no_trace = _run(None, _COUNTERS)
    assert hybrid.kernel_roofline(no_trace, "gdn_", "gated_delta_rule",
                                  "linear") is None
    # a program without such ops (the parent commit's), or without sizes
    other = _run(dict(_TRACE, op_seconds={"jit_step:fusion.1_f32[8]": 0.5}),
                 {"calls_in_trace": 2})
    assert hybrid.kernel_roofline(other, "gdn_", "gated_delta_rule",
                                  "linear") is None
    assert hybrid.kernel_roofline(other, "attn_flash", "gqa_attention",
                                  "full") is None
    assert train.op_share_of_step(other, "gdn_", "jit_step") is None
    assert train.scope_share_of_step(other, ["mla."], "jit_step") is None
    for counters in ({"calls_in_trace": 2}, dict(_COUNTERS, calls_in_trace=0)):
        assert hybrid.kernel_roofline(_run(_TRACE, counters), "gdn_",
                                      "gated_delta_rule", "linear") is None


# -- what the comparison has to fail -------------------------------------------------

def _dry(*extra):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 7), "--seconds", "0.5", "--cpu-dry-run",
         *extra], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", ["0", "1"])
def test_dry_run_is_correct_and_names_what_it_would_report(trace):
    out = _dry("--trace", trace)
    assert out["correct"] is True, out["checks"]
    assert out["dry_run"]["counts"]["tokens_per_step"] == 64
    assert {c["name"] for c in out["checks"]} >= {
        "grad_norm_gap", "delta_norm_gap", "routing_disagreement",
        "dropped_assignments", "last_loss_not_finite"}
    if trace == "1":      # no trace on a CPU: the program counter alone
        assert out["dry_run"]["would_report"] == [
            "expert_load_max_over_mean.lin3full1_8k"]
        # the table of scopes is built from the CPU-compiled step
        assert {"gdn.in_proj", "gdn.conv", "gdn.rule", "gdn.out",
                "gattn.qkv", "gattn.out", "moe.router", "moe.sort",
                "moe.experts", "moe.combine", "moe.shared",
                "-"} <= set(out["dry_run"]["scopes"])
    else:
        assert "scopes" not in out["dry_run"]


@pytest.mark.parametrize("control", CONTROLS)
def test_each_control_is_not_correct(control):
    out = _dry("--control", control)
    assert out["correct"] is False, out["checks"]
    assert {c["name"] for c in out["checks"]} >= {
        "grad_norm_gap", "delta_norm_gap", "routing_disagreement",
        "dropped_assignments"}


@pytest.mark.parametrize("fault", ["state_unchanged", "tokens_dropped"])
def test_a_fault_in_the_program_is_not_correct(fault, monkeypatch, capsys):
    """The program broken underneath the driver: a step that leaves the
    state as it was, and an expert layer that loses rows and says so in its
    stats."""
    from brpc_tpu.models import hybrid as model
    real_step, real_moe = model.make_train_step, model.moe_mlp
    if fault == "state_unchanged":
        def broken(cfg, optimizer):
            step = real_step(cfg, optimizer)
            return lambda p, s, t: (p, s, *step(p, s, t)[2:])
        monkeypatch.setattr(model, "make_train_step", broken)
    else:
        def lossy(cfg, y, lp):
            out, stats = real_moe(cfg, y, lp)
            return out, dict(stats, dropped=stats["dropped"] + 3)
        monkeypatch.setattr(model, "moe_mlp", lossy)
    assert bench_run.main(["--workload", CELL, "--seed", "77", "--seconds",
                           "0.5", "--cpu-dry-run"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is False, out["checks"]
    failed = {c["name"] for c in out["checks"] if c["number"] > c["limit"]}
    assert failed >= ({"delta_norm_gap"} if fault == "state_unchanged"
                      else {"dropped_assignments"})
