"""The Laguna-XS.2 training cell's own tests: its configuration keeps the
catalog's keys, its manifest entries resolve, its work functions agree with
hand counts, its readers read a hand-made reduced trace (and nothing where
there is none), and the comparison that decides ``correct`` fails what it
has to fail: the lower-precision control and each planted fault, on the CPU
at the dry-run size.
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
import work_swa  # noqa: E402
from readers import device, train, windowed  # noqa: E402

CONFIG_NAME = "train_laguna_xs2_33b_a3b_1chip"
MIX = "swa3full1_8k"
CELL = CONFIG_NAME + "." + MIX
with open(os.path.join(BENCH, "configs", CONFIG_NAME + ".json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

_PERIOD = ["full_attention"] + ["sliding_attention"] * 3
# poolside/Laguna-XS.2 config.json, as the model-configs catalog holds it.
CATALOG = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": _PERIOD * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
}
LAYER_METRICS = ("step_mfu", "device_idle_share", "step_gap_ms_p50",
            "opt_step_share", "unscoped_step_share", "band_attn_roofline",
            "full_attn_roofline", "band_attn_step_share",
            "full_attn_step_share", "moe_gmm_roofline",
            "moe_scope_step_share", "expert_load_max_over_mean",
            "band_pairs_visited_over_needed",
            # after the review: the float32 passes around attention that the
            # cell's own trace names as the next target, and the row movers
            "attn_glue_step_share", "moe_rows_step_share")
CONTROLS = ("lowprec", "half_batch", "no_window", "window_256", "one_rope",
            "no_yarn_scale", "no_attn_gate", "drop_eighth", "no_scaling")
ASSUMED = ("router_experts", "hidden_act", "gating", "router", "qk_norm",
           "rope_lanes", "yarn", "losses")


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
            CONFIG["vocab_size"]) == (5, 16, 12544)
    assert CONFIG["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert CONFIG["router_experts"] == CATALOG["num_experts"]
    # the layers held: the dense full layer 0 and one whole period after it
    held = CONFIG["num_hidden_layers"]
    assert CONFIG["layer_types"][:held] == _PERIOD + ["full_attention"]
    assert CONFIG["mlp_layer_types"][:held] == ["dense"] + ["sparse"] * 4
    assert CONFIG["num_attention_heads_per_layer"][:held] == [48, 64, 64, 64,
                                                              48]
    assert entry["source"] == CONFIG["source"] == \
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
    assert set(CONFIG["reduced_why"]) == reduced
    assert "16 chips" in CONFIG["stands_for"]
    assert set(ASSUMED) <= set(CONFIG["assumed"])
    for text in ("stands_for", "limits_why"):
        assert CONFIG[text]


def test_manifest_entries_resolve():
    (cell,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    manifest, cell, config, traffic = bench_run.load_cell(CELL)
    assert config["driver"] == "train_windowed_step"
    assert os.path.exists(os.path.join(BENCH, "drivers",
                                       config["driver"] + ".py"))
    assert (traffic["batch"], traffic["sequence"],
            traffic["trace_seconds"]) == (1, 8192, 5)
    assert traffic["dry_run"]["sequence"] == 64
    assert config["dry_run"]["sliding_window"] == 16
    (tokens,) = [m for m in MANIFEST["end_to_end"]
                 if m["name"] == "tokens_per_s"]
    assert CELL in tokens["workloads"]


def test_the_cell_reports_tokens_per_s_and_its_layer_metrics():
    e2e = {m["name"] for m in bench_run.metrics_of(MANIFEST, "end_to_end",
                                                   CELL)}
    assert e2e == {"tokens_per_s", "setup_s"}
    metrics = bench_run.metrics_of(MANIFEST, "per_layer", CELL, e2e)
    # the fifteen it came with, among whatever later PRs append
    assert {f"{n}.{MIX}" for n in LAYER_METRICS} <= {m["name"] for m in metrics}
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
    # the band: the first 512 queries see 1..512 keys, the other 7,680 see
    # 512: 131,328 + 3,932,160 pairs a head, 12.1% of the causal mask's
    assert work_swa.visible_pairs(t, 512) == 4_063_488 == \
        512 * 513 // 2 + 7680 * 512
    assert work_swa.visible_pairs(t, t) == work_swa.visible_pairs(t, 10 ** 6) \
        == t * (t + 1) // 2 == 33_558_528
    assert work_swa.visible_pairs(64, 16) == 16 * 17 // 2 + 48 * 16
    band = work_swa.band_attention(m, 1, t)
    assert band["flops"] == 6 * 2 * 64 * 4_063_488 * 128
    assert 399e9 < band["flops"] < 400e9     # 133 GFLOP forward, x 3
    assert band["bytes"] == 2 * t * 128 * (4 * 64 + 4 * 8)
    full = work_swa.gqa_attention(m, 1, t)
    assert full["flops"] == 6 * 2 * 48 * 33_558_528 * 128
    assert full["bytes"] == 2 * t * 128 * (4 * 48 + 4 * 8)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.roofline_seconds(band, peak) == band["flops"] / 197e12
    assert work.roofline_seconds(full, peak) == full["flops"] / 197e12
    assert work_swa.layer_counts(m) == (3, 2)
    assert work_swa.layer_counts(dict(m, num_hidden_layers=40)) == (30, 10)
    # the grouped products: the kanana cell's function at this cell's sizes
    routed = work_dsv3.routed_experts(m, 4096)
    assert routed["flops"] == 9 * 2 * 4096 * 2048 * 512
    assert routed["bytes"] == 9 * 2 * (4096 * (2048 + 512) + 16 * 2048 * 512)
    # per token and forward pass
    window = (2 * (2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64 + 8192 * 2048))
    full_l = (2 * (2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48 + 6144 * 2048))
    dense = 2 * 3 * 2048 * 8192
    moe = 2 * (2048 * 256 + 3 * 2048 * 512)
    head = 2 * 2048 * 12544
    rows = [4000, 4100, 4200, 4300]
    fwd = (t * (3 * window + 2 * full_l + dense + 4 * moe + head)
           + 3 * 2 * 2 * 64 * 4_063_488 * 128
           + 2 * 2 * 2 * 48 * 33_558_528 * 128
           + sum(2 * 3 * r * 2048 * 512 for r in rows))
    step = work_swa.windowed_train_step(m, 1, t, rows)
    assert step == {"flops": 3 * fwd, "tokens": t}
    assert 19.3e12 < step["flops"] < 19.5e12          # 19.4 TFLOP a step


# -- the readers, on a hand-made reduced trace --------------------------------------

def _run(trace, counters):
    return {"trace": trace, "peak": {"bf16_flops_per_s": 197e12,
                                     "hbm_bytes_per_s": 819e9},
            "outcome": types.SimpleNamespace(counters=counters)}


_SIZES = dict(CONFIG, n_routed_experts=CONFIG["num_experts"])
_COUNTERS = {"calls_in_trace": 2, "sizes": _SIZES, "batch": 1,
             "sequence": 8192, "step_flops": 20.4e12,
             "routed_rows": [[4096] * 4, [4000] * 4, [1] * 4],
             "series": {"expert_load_max_over_mean": [1.2, 1.1, 1.4],
                        "band_pairs_visited_over_needed": [2.0]}}
_TRACE = {
    "window_s": 1.0, "busy_s": 0.99, "module_gaps_s": [1e-5, 3e-5],
    "module_seconds": {"jit_step": 0.6},
    "op_seconds": {
        "jit_step:attn_band_fwd.1_bf16[1,64,8192,128]": 0.040,
        "jit_step:attn_band_bwd.2_bf16[1,64,8192,128]": 0.062,
        "jit_step:attn_flash_fwd.3_bf16[1,48,8192,128]": 0.040,
        "jit_step:attn_flash_bwd.4_bf16[1,48,8192,128]": 0.070,
        "jit_step:moe_gmm_fwd.5_bf16[73728,512]": 0.010,
        "jit_step:moe_gmm_dlhs.6_bf16[73728,2048]": 0.006,
        "jit_step:moe_gmm_drhs.7_bf16[16,2048,512]": 0.004,
        "jit_step:moe_rows_gather.8_bf16[73728,16,128]": 0.009,
        "jit_step:moe_rows_combine.9_bf16[8192,2048]": 0.003,
        "jit_step:while.3_s32__": 0.5,
        "jit_other:attn_band_fwd.1_bf16[8,8]": 0.002,
    },
    "scope_seconds": {"jit_step": {
        "swa.attn/attn.band_fwd/attn_band_fwd": {"fwd": 0.04},
        "swa.attn/attn.band_bwd/attn_band_bwd": {"bwd": 0.06},
        "swa.attn/attn.layout": {"fwd": 0.004, "remat": 0.004, "bwd": 0.008},
        "full.attn/attn.flash_fwd/attn_flash_fwd": {"fwd": 0.04},
        "full.attn/attn.flash_bwd/attn_flash_bwd": {"bwd": 0.07},
        "swa.rope": {"fwd": 0.01, "remat": 0.01, "bwd": 0.012},
        "swa.out": {"fwd": 0.02, "remat": 0.02, "bwd": 0.016},
        "full.rope": {"fwd": 0.005, "remat": 0.005, "bwd": 0.006},
        "full.out": {"fwd": 0.01, "remat": 0.01, "bwd": 0.012},
        "moe.experts/moe_gmm_fwd": {"fwd": 0.01},
        "moe.router": {"fwd": 0.02, "remat": 0.01, "bwd": 0.01},
        "opt.update": {"outside": 0.03},
        "-": {"outside": 0.02, "fwd": 0.02, "bwd": 0.01, "none": 0.01}}}}


def test_readers_on_a_hand_made_trace():
    run = _run(_TRACE, _COUNTERS)
    peak = run["peak"]
    band = work.roofline_seconds(work_swa.band_attention(_SIZES, 1, 8192),
                                 peak)
    assert windowed.kernel_roofline(run, "attn_band", "band_attention",
                                    "window") == \
        pytest.approx(100 * band * 2 * 3 / 0.104)
    full = work.roofline_seconds(work_swa.gqa_attention(_SIZES, 1, 8192),
                                 peak)
    assert windowed.kernel_roofline(run, "attn_flash", "gqa_attention",
                                    "full") == \
        pytest.approx(100 * full * 2 * 2 / 0.110)
    least = 4 * sum(work.roofline_seconds(
        work_dsv3.routed_experts(_SIZES, r), peak) for r in (4096, 4000))
    assert train.kernel_roofline(run, "moe_gmm", "routed_experts") == \
        pytest.approx(100 * least / 0.020)
    assert train.scope_share_of_step(run, ["swa.attn"], "jit_step") == \
        pytest.approx(100 * 0.116 / 0.6)
    assert train.scope_share_of_step(run, ["full.attn"], "jit_step") == \
        pytest.approx(100 * 0.11 / 0.6)
    assert train.scope_share_of_step(run, ["moe."], "jit_step") == \
        pytest.approx(100 * 0.05 / 0.6)
    assert train.scope_share_of_step(
        run, ["swa.rope", "swa.out", "full.rope", "full.out"],
        "jit_step") == pytest.approx(100 * 0.136 / 0.6)
    assert train.op_share_of_step(run, "moe_rows", "jit_step") == \
        pytest.approx(100 * 0.012 / 0.6)
    assert train.scope_share_of_step(
        run, ["opt.update", "-"], "jit_step", phases=["outside"]) == \
        pytest.approx(100 * 0.05 / 0.6)
    assert train.scope_share_of_step(run, ["-"], "jit_step") == \
        pytest.approx(100 * 0.06 / 0.6)
    assert train.stats_median(run, "band_pairs_visited_over_needed") == 2.0
    assert device.step_mfu(run) == pytest.approx(
        100 * 20.4e12 * 2 / 1.0 / 197e12)
    read = {}
    for metric in MANIFEST["per_layer"]:
        if CELL in metric.get("workloads", ()):
            value = bench_run.read_layer_metric(metric["name"], dict(
                run, ctx=None))
            assert value is not None and value > 0, metric["name"]
            if metric["unit"] == "%":
                assert value <= 100, metric["name"]
            read[metric["name"]] = value
    assert {f"{n}.{MIX}" for n in LAYER_METRICS} <= set(read)


def test_readers_return_none_where_there_is_nothing_to_read():
    no_trace = _run(None, _COUNTERS)
    assert windowed.kernel_roofline(no_trace, "attn_band", "band_attention",
                                    "window") is None
    # a program without such ops (the parent commit's), or without sizes
    other = _run(dict(_TRACE, op_seconds={"jit_step:fusion.1_f32[8]": 0.5},
                      scope_seconds={"jit_step": {"-": {"fwd": 0.1}}}),
                 {"calls_in_trace": 2})
    assert windowed.kernel_roofline(other, "attn_band", "band_attention",
                                    "window") is None
    assert windowed.kernel_roofline(other, "attn_flash", "gqa_attention",
                                    "full") is None
    assert train.scope_share_of_step(other, ["swa.attn"], "jit_step") is None
    assert train.stats_median(other, "band_pairs_visited_over_needed") is None
    for counters in ({"calls_in_trace": 2}, dict(_COUNTERS, calls_in_trace=0)):
        assert windowed.kernel_roofline(_run(_TRACE, counters), "attn_band",
                                        "band_attention", "window") is None


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
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
        "grad_norm_gap", "matrix_grad_norm_gap", "delta_norm_gap",
        "routing_disagreement", "dropped_assignments", "last_loss_not_finite"}
    if trace == "1":      # no trace on a CPU: the program counter alone
        # (attention runs dense at the dry run's heads of 32, so the step
        # holds no band call to count visited pairs from)
        assert out["dry_run"]["would_report"] == [
            f"expert_load_max_over_mean.{MIX}"]
        # the table of scopes is built from the CPU-compiled step
        assert {"swa.qkv", "swa.rope", "swa.attn/attn.dense", "swa.out",
                "full.qkv", "full.rope", "full.attn/attn.dense", "full.out",
                "dense.mlp", "moe.router", "moe.sort", "moe.experts",
                "moe.combine", "moe.shared", "windowed.glue",
                "-"} <= set(out["dry_run"]["scopes"])
    else:
        assert "scopes" not in out["dry_run"]


@pytest.mark.parametrize("control", CONTROLS)
def test_each_control_is_not_correct(control):
    out = _dry("--control", control)
    assert out["correct"] is False, out["checks"]
    assert {c["name"] for c in out["checks"]} >= {
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
        "grad_norm_gap", "delta_norm_gap", "routing_disagreement",
        "dropped_assignments"}
    if control == "half_batch":     # the fault the losses' limits are set by
        assert any(c["number"] > c["limit"] for c in out["checks"]
                   if c["name"].startswith("loss_gap_step")), out["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "tokens_dropped",
                                   "window_not_applied"])
def test_a_fault_in_the_program_is_not_correct(fault, monkeypatch, capsys):
    """The program broken underneath the driver: a step that leaves the
    state as it was, an expert layer that loses rows and says so in its
    stats, and window layers that attend causally."""
    from brpc_tpu.models import windowed as model
    real_step, real_moe, real_attn = (model.make_train_step, model.moe_mlp,
                                      model.attention)
    if fault == "state_unchanged":
        def broken(cfg, optimizer):
            step = real_step(cfg, optimizer)
            return lambda p, s, t: (p, s, *step(p, s, t)[2:])
        monkeypatch.setattr(model, "make_train_step", broken)
    elif fault == "tokens_dropped":
        def lossy(cfg, y, lp):
            out, stats = real_moe(cfg, y, lp)
            return out, dict(stats, dropped=stats["dropped"] + 3)
        monkeypatch.setattr(model, "moe_mlp", lossy)
    else:
        monkeypatch.setattr(model, "attention",
                            lambda q, k, v, window: real_attn(q, k, v))
    assert bench_run.main(["--workload", CELL, "--seed", "77", "--seconds",
                           "0.5", "--cpu-dry-run"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is False, out["checks"]
    failed = {c["name"] for c in out["checks"] if c["number"] > c["limit"]}
    assert failed >= {"state_unchanged": {"delta_norm_gap"},
                      "tokens_dropped": {"dropped_assignments"},
                      "window_not_applied": {"matrix_grad_norm_gap"}}[fault]
