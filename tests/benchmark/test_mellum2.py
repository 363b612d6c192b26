"""The Mellum2 training cell's own tests: its configuration keeps the
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
import work_mellum  # noqa: E402
from readers import device, mellum, train  # noqa: E402

CONFIG_NAME = "train_mellum2_12b_a2_5b_1chip"
MIX = "swa3full1_e64_8k"
CELL = CONFIG_NAME + "." + MIX
with open(os.path.join(BENCH, "configs", CONFIG_NAME + ".json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# JetBrains/Mellum2-12B-A2.5B-Instruct config.json, as the model-configs
# catalog holds it.
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": _PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}
LAYER_METRICS = (
    "step_mfu", "device_idle_share", "step_gap_ms_p50", "band_attn_roofline",
    "full_attn_roofline", "moe_gmm_roofline", "band_attn_step_share",
    "full_attn_step_share", "attn_glue_step_share", "moe_rows_step_share",
    "moe_scope_step_share", "opt_step_share", "unscoped_step_share",
    "band_pairs_visited_over_needed", "expert_load_max_over_mean",
    # the three that read what is new
    "moe_router_step_share", "qk_norm_step_share", "moe_bound_in_use_share")
CONTROLS = ("lowprec", "half_batch", "window_512", "sigmoid_router",
            "no_qk_norm", "yarn_half_head", "full_first", "drop_eighth")
ASSUMED = ("router_experts", "qk_norm", "router", "shared_expert", "gating",
           "intermediate_size", "rope_lanes", "yarn", "losses", "optimizer",
           "init", "recomputation")


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
            CONFIG["vocab_size"]) == (4, 16, 24576)
    assert CONFIG["vocab_size"] * 4 == CATALOG["vocab_size"]
    assert CONFIG["router_experts"] == CATALOG["num_experts"]
    assert CONFIG["expert_offset"] == 0
    # the layers held: one whole period, the full layer last
    assert CONFIG["layer_types"][:CONFIG["num_hidden_layers"]] == _PERIOD
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/"
        "main/config.json")
    assert set(CONFIG["reduced_why"]) == reduced
    assert "28 chips" in CONFIG["stands_for"]
    assert set(ASSUMED) <= set(CONFIG["assumed"])
    for text in ("stands_for", "limits_why", "precision"):
        assert CONFIG[text]
    # no width is cut, at the dry size only
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "num_experts_per_tok", "sliding_window",
                "num_attention_heads", "num_key_value_heads"):
        assert CONFIG[key] == CATALOG[key] and key in CONFIG["dry_run"], key


def test_manifest_entries_resolve():
    (cell,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    manifest, cell, config, traffic = bench_run.load_cell(CELL)
    assert config["driver"] == "train_mellum_step"
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
    # the eighteen it came with, among whatever later PRs append
    assert {f"{n}.{MIX}" for n in LAYER_METRICS} <= {m["name"] for m in metrics}
    for m in metrics:
        assert m["moves"] == "tokens_per_s" and m["workloads"] == [CELL]
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert (spec["layer"], spec["unit"]) == (m["layer"], m["unit"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    by_name = {m["name"]: m for m in metrics}
    assert by_name[f"moe_bound_in_use_share.{MIX}"]["source"] == \
        "program_counter"


def test_the_driver_sets_each_switch_by_what_the_file_states():
    sys.path.insert(0, ROOT)
    from brpc_tpu.models import windowed
    from drivers import train_mellum_step

    m = {k: CONFIG[k] for k in train_mellum_step._SIZES}
    cfg = train_mellum_step.model_config(windowed, m)
    assert cfg == windowed.WindowedConfig.mellum2(
        n_layers=4, vocab_size=24576, n_held=16)
    assert cfg.layout == (0, 3, 1, 0)
    assert (cfg.attn_gate, cfg.qk_norm, cfg.router, cfg.shared_intermediate,
            cfg.full_rotary_factor) == (False, True, "softmax", 0, 1.0)
    with pytest.raises(SystemExit):
        train_mellum_step.model_config(windowed,
                                       dict(m, norm_topk_prob=False))
    # the leaves the matrices' limit reads: every projection, expert stack,
    # the embedding and the head; no norm, no router
    names = train_mellum_step._matrices(m)
    assert len(names) == 2 * 7 + 2
    assert not [n for n in names if "norm" in n or "router" in n]


# -- work, against hand counts ---------------------------------------------------

def test_work_counts_against_hand_worked_numbers():
    m, t = CONFIG, 8192
    # the band: the first 1,024 queries see 1..1,024 keys, the other 7,168
    # see 1,024: 524,800 + 7,340,032 pairs a head, 23.4% of the causal mask's
    pairs = 1024 * 1025 // 2 + 7168 * 1024
    assert pairs == 7_864_832
    band = work_mellum.band_attention(m, 1, t)
    assert band["flops"] == 6 * 2 * 32 * pairs * 128
    assert band["bytes"] == 2 * t * 128 * (4 * 32 + 4 * 4)
    full = work_mellum.gqa_attention(m, 1, t)
    assert full["flops"] == 6 * 2 * 32 * 33_558_528 * 128
    assert full["bytes"] == band["bytes"]
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.roofline_seconds(band, peak) == band["flops"] / 197e12
    assert work_mellum.layer_counts(m) == (3, 1)
    assert work_mellum.layer_counts(dict(m, num_hidden_layers=28)) == (21, 7)
    routed = work_mellum.routed_experts(m, 16384)
    assert routed["flops"] == 9 * 2 * 16384 * 2304 * 896
    assert routed["bytes"] == 9 * 2 * (16384 * (2304 + 896)
                                       + 16 * 2304 * 896)
    # 1,024 rows an expert: the products are bound by the MXU, not by HBM
    assert work.roofline_seconds(routed, peak) == routed["flops"] / 197e12
    # per token and forward pass: q and o 4,096 wide, k and v 512, the
    # router; no gate, no shared expert, no dense layer
    layer = 2 * (2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64)
    head = 2 * 2304 * 24576
    rows = [16000, 16400, 16384, 16900]
    fwd = (t * (4 * layer + head)
           + 3 * 2 * 2 * 32 * pairs * 128 + 2 * 2 * 32 * 33_558_528 * 128
           + sum(2 * 3 * r * 2304 * 896 for r in rows))
    step = work_mellum.mellum_train_step(m, 1, t, rows)
    assert step == {"flops": 3 * fwd, "tokens": t}
    assert 12.2e12 < step["flops"] < 12.3e12          # 12.2 TFLOP a step
    experts = 3 * sum(2 * 3 * r * 2304 * 896 for r in rows)
    assert 0.19 < experts / step["flops"] < 0.21      # a fifth of it


# -- the readers, on a hand-made reduced trace --------------------------------------

def _run(trace, counters):
    return {"trace": trace, "peak": {"bf16_flops_per_s": 197e12,
                                     "hbm_bytes_per_s": 819e9},
            "outcome": types.SimpleNamespace(counters=counters)}


_COUNTERS = {"calls_in_trace": 2, "sizes": CONFIG, "batch": 1,
             "sequence": 8192, "step_flops": 12.2e12,
             "routed_rows": [[16384] * 4, [16000] * 4, [1] * 4],
             "series": {"expert_load_max_over_mean": [1.2, 1.1, 1.4],
                        "moe_bound_in_use_share": [29.0, 29.4, 29.8],
                        "band_pairs_visited_over_needed": [1.5]}}
_TRACE = {
    "window_s": 1.0, "busy_s": 0.99, "module_gaps_s": [1e-5, 3e-5],
    "module_seconds": {"jit_step": 0.4},
    "op_seconds": {
        "jit_step:attn_band_fwd.1_bf16[1,32,8192,128]": 0.030,
        "jit_step:attn_band_bwd.2_bf16[1,32,8192,128]": 0.042,
        "jit_step:attn_flash_fwd.3_bf16[1,32,8192,128]": 0.020,
        "jit_step:attn_flash_bwd.4_bf16[1,32,8192,128]": 0.036,
        "jit_step:moe_gmm_fwd.5_bf16[69632,896]": 0.030,
        "jit_step:moe_gmm_dlhs.6_bf16[69632,2304]": 0.020,
        "jit_step:moe_gmm_drhs.7_bf16[16,2304,896]": 0.014,
        "jit_step:moe_rows_gather.8_bf16[69632,24,128]": 0.009,
        "jit_step:moe_rows_combine.9_bf16[8192,2304]": 0.003,
        "jit_step:while.3_s32__": 0.3,
        "jit_other:attn_band_fwd.1_bf16[8,8]": 0.002,
    },
    "scope_seconds": {"jit_step": {
        "swa.attn/attn.band_fwd/attn_band_fwd": {"fwd": 0.03},
        "swa.attn/attn.band_bwd/attn_band_bwd": {"bwd": 0.042},
        "swa.attn/attn.layout": {"fwd": 0.002, "remat": 0.002, "bwd": 0.004},
        "full.attn/attn.flash_fwd/attn_flash_fwd": {"fwd": 0.02},
        "full.attn/attn.flash_bwd/attn_flash_bwd": {"bwd": 0.036},
        "swa.qknorm": {"fwd": 0.003, "remat": 0.003, "bwd": 0.006},
        "full.qknorm": {"fwd": 0.001, "remat": 0.001, "bwd": 0.002},
        "swa.rope": {"fwd": 0.005, "remat": 0.005, "bwd": 0.006},
        "swa.out": {"fwd": 0.004, "remat": 0.004, "bwd": 0.008},
        "full.rope": {"fwd": 0.002, "remat": 0.002, "bwd": 0.002},
        "full.out": {"fwd": 0.001, "remat": 0.001, "bwd": 0.004},
        "moe.experts/moe_gmm_fwd": {"fwd": 0.03},
        "moe.router": {"fwd": 0.006, "remat": 0.002, "bwd": 0.004},
        "opt.update": {"outside": 0.03},
        "-": {"outside": 0.01, "fwd": 0.01, "bwd": 0.01, "none": 0.01}}}}


def test_readers_on_a_hand_made_trace():
    run = _run(_TRACE, _COUNTERS)
    peak = run["peak"]
    band = work.roofline_seconds(work_mellum.band_attention(CONFIG, 1, 8192),
                                 peak)
    assert mellum.kernel_roofline(run, "attn_band", "band_attention",
                                  "window") == \
        pytest.approx(100 * band * 2 * 3 / 0.074)     # every program's
    full = work.roofline_seconds(work_mellum.gqa_attention(CONFIG, 1, 8192),
                                 peak)
    assert mellum.kernel_roofline(run, "attn_flash", "gqa_attention",
                                  "full") == \
        pytest.approx(100 * full * 2 * 1 / 0.056)
    least = 4 * sum(work.roofline_seconds(
        work_mellum.routed_experts(CONFIG, r), peak) for r in (16384, 16000))
    assert mellum.kernel_roofline(run, "moe_gmm", "routed_experts") == \
        pytest.approx(100 * least / 0.064)
    assert train.scope_share_of_step(run, ["moe.router"], "jit_step") == \
        pytest.approx(100 * 0.012 / 0.4)
    assert train.scope_share_of_step(
        run, ["swa.qknorm", "full.qknorm"], "jit_step") == \
        pytest.approx(100 * 0.016 / 0.4)
    assert train.scope_share_of_step(
        run, ["swa.rope", "swa.out", "full.rope", "full.out"],
        "jit_step") == pytest.approx(100 * 0.044 / 0.4)
    assert train.stats_median(run, "moe_bound_in_use_share") == 29.4
    assert device.step_mfu(run) == pytest.approx(
        100 * 12.2e12 * 2 / 1.0 / 197e12)
    # the eighteen it came with, each through its own file (a metric a later
    # PR appends may read a span this hand-made trace does not hold)
    units = {m["name"]: m["unit"] for m in MANIFEST["per_layer"]}
    for name in (f"{n}.{MIX}" for n in LAYER_METRICS):
        value = bench_run.read_layer_metric(name, dict(run, ctx=None))
        assert value is not None and value > 0, name
        if units[name] == "%":
            assert value <= 100, name


def test_readers_return_none_where_there_is_nothing_to_read():
    no_trace = _run(None, _COUNTERS)
    assert mellum.kernel_roofline(no_trace, "attn_band", "band_attention",
                                  "window") is None
    # a program without such ops or scopes (the parent commit's), or
    # without sizes
    other = _run(dict(_TRACE, op_seconds={"jit_step:fusion.1_f32[8]": 0.5},
                      scope_seconds={"jit_step": {"-": {"fwd": 0.1}}}),
                 {"calls_in_trace": 2})
    for prefix, fn, layers in (("attn_band", "band_attention", "window"),
                               ("attn_flash", "gqa_attention", "full"),
                               ("moe_gmm", "routed_experts", None)):
        assert mellum.kernel_roofline(other, prefix, fn, layers) is None
    for name in ("moe_router_step_share", "qk_norm_step_share",
                 "moe_bound_in_use_share"):
        assert bench_run.read_layer_metric(
            f"{name}.{MIX}", dict(other, ctx=None)) is None, name
    for counters in ({"calls_in_trace": 2}, dict(_COUNTERS, calls_in_trace=0)):
        assert mellum.kernel_roofline(_run(_TRACE, counters), "attn_band",
                                      "band_attention", "window") is None


# -- what the comparison has to fail -------------------------------------------------

def _dry(*extra):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 7), "--seconds", "0.5", "--cpu-dry-run",
         *extra], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.parametrize("trace", ["0", "1"])
def test_dry_run_is_correct_and_names_what_it_would_report(trace):
    out, err = _dry("--trace", trace)
    assert out["correct"] is True, out["checks"]
    assert out["dry_run"]["counts"]["tokens_per_step"] == 64
    assert {c["name"] for c in out["checks"]} >= {
        "grad_norm_gap", "matrix_grad_norm_gap", "delta_norm_gap",
        "routing_disagreement", "dropped_assignments", "last_loss_not_finite"}
    assert err.count("benchmark: loss step") == 3        # the losses, printed
    if trace == "1":      # no trace on a CPU: the program counters alone
        # (attention runs dense at the dry run's heads of 32, so the step
        # holds no band call to count visited pairs from)
        assert set(out["dry_run"]["would_report"]) >= {
            f"expert_load_max_over_mean.{MIX}",
            f"moe_bound_in_use_share.{MIX}"}
        assert not [n for n in out["dry_run"]["would_report"]
                    if "_step_share" in n or "roofline" in n or "mfu" in n]
        # the table of scopes is built from the CPU-compiled step
        scopes = set(out["dry_run"]["scopes"])
        assert {"swa.qkv", "swa.qknorm", "swa.rope", "swa.attn/attn.dense",
                "swa.out", "full.qkv", "full.qknorm", "full.rope",
                "full.attn/attn.dense", "full.out", "moe.router", "moe.sort",
                "moe.experts", "moe.combine", "windowed.glue", "embed",
                "weights.cast", "loss.chunk", "opt.update", "-"} <= scopes
        assert not {"moe.shared", "dense.mlp"} & scopes
    else:
        assert "scopes" not in out["dry_run"]
        assert out["dry_run"]["would_report"] == ["setup_s", "tokens_per_s"]


@pytest.mark.parametrize("control", CONTROLS)
def test_each_control_is_not_correct(control):
    out, _ = _dry("--control", control)
    assert out["correct"] is False, out["checks"]
    assert {c["name"] for c in out["checks"]} >= {
        "grad_norm_gap", "matrix_grad_norm_gap", "delta_norm_gap",
        "routing_disagreement", "dropped_assignments"}


@pytest.mark.parametrize("fault", ["state_unchanged", "tokens_dropped",
                                   "full_layer_first", "shared_put_back"])
def test_a_fault_in_the_program_is_not_correct(fault, monkeypatch, capsys):
    """The program broken underneath the driver: a step that leaves the
    state as it was, an expert layer that loses rows and says so in its
    stats, a stack laid out by Laguna-XS.2's rule (the full layer first in
    its period), and an expert layer that adds something beside its routed
    experts."""
    from brpc_tpu.models import windowed as model
    real_step, real_moe, real_block = (model.make_train_step, model.moe_mlp,
                                       model.attention_block)
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
    elif fault == "full_layer_first":
        order = iter(["full", "window"])        # the two bodies, as traced
        monkeypatch.setattr(
            model, "attention_block", lambda cfg, kind, x, lp, positions:
            real_block(cfg, next(order, kind), x, lp, positions))
    else:
        def shared(cfg, y, lp):
            out, stats = real_moe(cfg, y, lp)
            return out + 0.05 * y, stats
        monkeypatch.setattr(model, "moe_mlp", shared)
    assert bench_run.main(["--workload", CELL, "--seed", "77", "--seconds",
                           "0.5", "--cpu-dry-run"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is False, out["checks"]
    failed = {c["name"] for c in out["checks"] if c["number"] > c["limit"]}
    assert failed >= {"state_unchanged": {"delta_norm_gap"},
                      "tokens_dropped": {"dropped_assignments"},
                      "full_layer_first": {"matrix_grad_norm_gap"},
                      "shared_put_back": {"matrix_grad_norm_gap"}}[fault]
