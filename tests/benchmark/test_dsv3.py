"""The kanana-2-30b-a3b training cell's own tests: its configuration keeps
the catalog's keys, its work functions agree with hand counts, its readers
read a hand-made reduced trace (and nothing where there is none), and the
comparison that decides ``correct`` fails what it has to fail: the
lower-precision control and each planted fault, on the CPU at the dry-run
size.
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
from readers import device, train  # noqa: E402

CELL = "train_kanana2_30b_a3b_1chip.seq8k"
with open(os.path.join(BENCH, "configs",
                       "train_kanana2_30b_a3b_1chip.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

# kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json, as the model-configs
# catalog holds it.
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 128256,
}


def test_the_config_keeps_the_catalog_s_keys():
    (entry,) = [c for c in MANIFEST["configs"]
                if c["name"] == "train_kanana2_30b_a3b_1chip"]
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in CATALOG.items():
        if key in reduced:
            assert CONFIG["published"][key] == value, key
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (5, 16, 16032)
    assert CONFIG["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert CONFIG["router_experts"] == CATALOG["n_routed_experts"]
    assert entry["source"] == CONFIG["source"]


def test_the_cell_reports_tokens_per_s_and_seven_layer_metrics():
    """The seven it came with, among whatever later PRs appended (that
    each entry resolves to a file and a reader: ``test_benchmark.py``)."""
    e2e = {m["name"] for m in bench_run.metrics_of(MANIFEST, "end_to_end",
                                                   CELL)}
    assert e2e == {"tokens_per_s", "setup_s"}
    metrics = bench_run.metrics_of(MANIFEST, "per_layer", CELL, e2e)
    assert {n + ".seq8k" for n in (
        "step_mfu", "device_idle_share", "step_gap_ms_p50",
        "mla_attn_roofline", "moe_gmm_roofline", "moe_step_share",
        "expert_load_max_over_mean")} <= {m["name"] for m in metrics}
    assert all(m["workloads"] == [CELL] for m in metrics)


# -- work, against hand counts ---------------------------------------------------

def test_work_counts_against_hand_worked_numbers():
    m = CONFIG
    t = 8192
    # attention: 32 heads, t(t+1)/2 pairs, QK^T over 192 and PV over 128,
    # forward and twice that backward
    pairs = 32 * t * (t + 1) // 2
    attn = work_dsv3.mla_attention(m, 1, t)
    assert attn["flops"] == 3 * 2 * pairs * (192 + 128)
    assert attn["bytes"] == 2 * 32 * t * 4 * (192 + 128)
    # the grouped products over 6,144 rows: 9 products of 2*rows*2048*768
    routed = work_dsv3.routed_experts(m, 6144)
    assert routed["flops"] == 9 * 2 * 6144 * 2048 * 768
    assert routed["bytes"] == 9 * 2 * (6144 * (2048 + 768)
                                       + 16 * 2048 * 768)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.roofline_seconds(routed, peak) == routed["bytes"] / 819e9
    # per token and forward pass, the issue's count in MFLOP
    mla = 2 * (2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048)
    scores = 2 * 320 * 32 * (t + 1) // 2
    dense = 2 * 3 * 2048 * 6144
    moe = 2 * 2048 * 128 + 2 * 3 * 2048 * 1536
    head = 2 * 2048 * 16032
    rows = [6000, 6100, 6200, 6300]
    fwd = t * (5 * (mla + scores) + dense + 4 * moe + head) + sum(
        2 * 3 * r * 2048 * 768 for r in rows)
    step = work_dsv3.dsv3_train_step(m, 1, t, rows)
    assert step == {"flops": 3 * fwd, "tokens": t}
    assert 2.78e9 < step["flops"] / t < 2.80e9      # 2.79 GFLOP a token


# -- the readers, on a hand-made reduced trace --------------------------------------

def _run(trace, counters):
    return {"trace": trace, "peak": {"bf16_flops_per_s": 197e12,
                                     "hbm_bytes_per_s": 819e9},
            "outcome": types.SimpleNamespace(counters=counters)}


_COUNTERS = {"calls_in_trace": 2, "sizes": CONFIG, "batch": 1,
             "sequence": 8192, "step_flops": 22.9e12,
             "routed_rows": [[6144] * 4, [6000] * 4, [1] * 4],
             "series": {"expert_load_max_over_mean": [1.2, 1.1, 1.4]}}
_TRACE = {
    "window_s": 1.0, "busy_s": 0.99, "module_gaps_s": [1e-5, 3e-5],
    "module_seconds": {"jit_step": 0.8},
    "op_seconds": {
        "jit_step:attn_flash_fwd.1_bf16[1,32,8192,128]": 0.10,
        "jit_step:attn_flash_bwd_dq.2_bf16[1,32,8192,192]": 0.10,
        "jit_step:attn_flash_bwd_dkv.3_bf16[1,32,8192,192]": 0.12,
        "jit_step:moe_gmm_fwd.4_bf16[53248,768]": 0.010,
        "jit_step:moe_gmm_dlhs.5_bf16[53248,2048]": 0.006,
        "jit_step:moe_gmm_drhs.6_bf16[16,2048,768]": 0.004,
        "jit_step:moe_rows_gather.7_bf16[53248,16,128]": 0.004,
        "jit_step:moe_rows_combine.8_bf16[8192,2048]": 0.002,
        "jit_step:while.3_s32__": 0.7,
        "jit_other:moe_gmm_fwd.4_bf16[8,8]": 0.002,
    },
    # self seconds by the program's scope and phase (trace_reduce, given the
    # compiled step's text)
    "scope_seconds": {"jit_step": {
        "mla.q_proj": {"fwd": 0.02, "remat": 0.02, "bwd": 0.04},
        "mla.kv_up": {"fwd": 0.01, "bwd": 0.02},
        "mla.out_proj": {"bwd": 0.01},
        "attn.flash_fwd/attn_flash_fwd": {"fwd": 0.10},
        "moe.experts/moe_gmm_fwd": {"fwd": 0.005, "remat": 0.005},
        "moe.router": {"fwd": 0.01, "bwd": 0.02},
        "moe.sort": {"fwd": 0.02, "outside": 0.001},
        "-": {"outside": 0.06, "fwd": 0.03, "bwd": 0.05, "none": 0.004}}}}


def test_readers_on_a_hand_made_trace():
    run = _run(_TRACE, _COUNTERS)
    peak = run["peak"]
    attn = work.roofline_seconds(work_dsv3.mla_attention(CONFIG, 1, 8192),
                                 peak)
    assert train.kernel_roofline(run, "attn_flash", "mla_attention") == \
        pytest.approx(100 * attn * 2 * 5 / 0.32)
    least = 4 * sum(work.roofline_seconds(
        work_dsv3.routed_experts(CONFIG, r), peak) for r in (6144, 6000))
    assert train.kernel_roofline(run, "moe_gmm", "routed_experts") == \
        pytest.approx(100 * least / 0.022)
    assert train.op_share_of_step(run, "moe_gmm", "jit_step") == \
        pytest.approx(100 * 0.022 / 0.8)
    assert train.op_share_of_step(run, "moe_rows", "jit_step") == \
        pytest.approx(100 * 0.006 / 0.8)
    assert train.scope_share_of_step(
        run, ["mla.q_proj", "mla.kv_down", "mla.kv_up", "mla.out_proj"],
        "jit_step") == pytest.approx(100 * 0.12 / 0.8)
    assert train.scope_share_of_step(run, ["moe."], "jit_step") == \
        pytest.approx(100 * 0.061 / 0.8)
    assert train.scope_share_of_step(
        run, ["opt.update", "-"], "jit_step", phases=["outside"]) == \
        pytest.approx(100 * 0.06 / 0.8)
    assert train.scope_share_of_step(run, ["-"], "jit_step") == \
        pytest.approx(100 * 0.144 / 0.8)
    assert train.stats_median(run, "expert_load_max_over_mean") == 1.2
    assert device.step_mfu(run) == pytest.approx(
        100 * 22.9e12 * 2 / 1.0 / 197e12)
    for metric in MANIFEST["per_layer"]:
        if CELL in metric.get("workloads", ()):
            value = bench_run.read_layer_metric(metric["name"], dict(
                run, ctx=None))
            assert value is not None and value > 0, metric["name"]
            if metric["unit"] == "%":
                assert value <= 100, metric["name"]


def test_readers_return_none_where_there_is_nothing_to_read():
    no_trace = _run(None, _COUNTERS)
    assert train.kernel_roofline(no_trace, "moe_gmm", "routed_experts") is None
    assert train.op_share_of_step(no_trace, "moe_gmm", "jit_step") is None
    # a program without such ops (the parent commit's), or without stats
    other = _run(dict(_TRACE, op_seconds={"jit_step:fusion.1_f32[8]": 0.5}),
                 {"calls_in_trace": 2})
    assert train.kernel_roofline(other, "moe_gmm", "routed_experts") is None
    assert train.kernel_roofline(other, "attn_flash", "mla_attention") is None
    assert train.op_share_of_step(other, "moe_gmm", "jit_step") is None
    assert train.scope_share_of_step(other, ["gdn."], "jit_step") is None
    assert train.stats_median(other, "expert_load_max_over_mean") is None
    # the kernels' ops but no sizes to count their work from
    assert train.kernel_roofline(_run(_TRACE, {"calls_in_trace": 2}),
                                 "attn_flash", "mla_attention") is None


# -- what the comparison has to fail -------------------------------------------------

def _dry(*extra):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 7), "--seconds", "0.5", "--cpu-dry-run",
         *extra], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("control", ["lowprec", "half_batch", "drop_sixth",
                                     "no_scaling"])
def test_each_control_is_not_correct(control):
    out = _dry("--control", control)
    assert out["correct"] is False, out["checks"]
    assert {c["name"] for c in out["checks"]} >= {
        "grad_norm_gap", "delta_norm_gap", "routing_disagreement",
        "dropped_assignments"}


@pytest.mark.parametrize("fault", ["state_unchanged", "tokens_dropped"])
def test_a_fault_in_the_program_is_not_correct(fault, monkeypatch, capsys):
    """The program broken underneath the driver: a step that leaves the
    state as it was, and an expert layer that loses the rows past a
    capacity and says so in its stats."""
    from brpc_tpu.models import deepseek
    real_step, real_moe = deepseek.make_train_step, deepseek.moe_mlp
    if fault == "state_unchanged":
        def broken(cfg, optimizer):
            step = real_step(cfg, optimizer)
            return lambda p, s, t: (p, s, *step(p, s, t)[2:])
        monkeypatch.setattr(deepseek, "make_train_step", broken)
    else:
        def lossy(cfg, y, lp):
            out, stats = real_moe(cfg, y, lp)
            return out, dict(stats, dropped=stats["dropped"] + 3)
        monkeypatch.setattr(deepseek, "moe_mlp", lossy)
    assert bench_run.main(["--workload", CELL, "--seed", "77", "--seconds",
                           "0.5", "--cpu-dry-run"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is False, out["checks"]
    failed = {c["name"] for c in out["checks"] if c["number"] > c["limit"]}
    assert failed >= ({"delta_norm_gap"} if fault == "state_unchanged"
                      else {"dropped_assignments"})
