"""The granite-4.0-h-micro training cell's own tests: its configuration keeps
the catalog's keys, its manifest entries resolve, its work functions agree
with hand counts, its readers read nothing where there is nothing, and the
step its dry run compiles names the Mamba-2 layer's scopes.
"""

import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
import work_mamba  # noqa: E402
from readers import device, mamba, train  # noqa: E402

CONFIG_NAME = "train_granite4_h_micro_1chip"
MIX = "ssd9attn1_4k"
CELL = CONFIG_NAME + "." + MIX
with open(os.path.join(BENCH, "configs", CONFIG_NAME + ".json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

_PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# ibm-granite/granite-4.0-h-micro config.json, as the model-configs catalog
# holds it.
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": _PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
}
LAYER_METRICS = ("step_mfu", "device_idle_share", "step_gap_ms_p50",
                 "ssd_roofline", "ssd_step_share", "ssm_mixer_step_share",
                 "attn_roofline")
SSM_SCOPES = {"ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.norm", "ssm.out",
              "ssm.glue", "gqa.qkv", "gqa.out", "mlp", "embed",
              "weights.cast", "loss.chunk", "opt.update"}


def test_the_config_keeps_the_catalog_s_keys():
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG_NAME]
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "vocab_size"}
    for key, value in CATALOG.items():
        if key in reduced:
            assert CONFIG["published"][key] == value, key
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["vocab_size"]) == (10, 25088)
    assert CONFIG["vocab_size"] * 4 == CATALOG["vocab_size"]
    # the layers held: one whole period, in the published order
    assert CONFIG["layer_types"][:CONFIG["num_hidden_layers"]] == _PERIOD
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
        "config.json")
    assert set(CONFIG["reduced_why"]) == reduced
    assert "4 chips" in CONFIG["stands_for"] and \
        "arXiv:2411.05288" in CONFIG["stands_for"]
    for text in ("stands_for", "limits_why", "precision", "aot"):
        assert CONFIG[text]
    assert {"init", "mamba_init", "optimizer", "time_step_limit",
            "compute"} <= set(CONFIG["assumed"])
    # no width is cut, at the dry size only
    for key in ("hidden_size", "mamba_n_heads", "mamba_d_head",
                "mamba_d_state", "shared_intermediate_size",
                "num_attention_heads", "num_key_value_heads"):
        assert key in CONFIG["dry_run"], key


def test_manifest_entries_resolve():
    (cell,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    manifest, cell, config, traffic = bench_run.load_cell(CELL)
    assert config["driver"] == "train_mamba_step"
    assert os.path.exists(os.path.join(BENCH, "drivers",
                                       config["driver"] + ".py"))
    assert (traffic["batch"], traffic["sequence"],
            traffic["trace_seconds"]) == (1, 4096, 5)
    assert traffic["dry_run"]["sequence"] == 64
    (tokens,) = [m for m in MANIFEST["end_to_end"]
                 if m["name"] == "tokens_per_s"]
    assert CELL in tokens["workloads"]
    e2e = {m["name"] for m in bench_run.metrics_of(MANIFEST, "end_to_end",
                                                   CELL)}
    assert e2e == {"tokens_per_s", "setup_s"}
    metrics = bench_run.metrics_of(MANIFEST, "per_layer", CELL, e2e)
    assert {f"{n}.{MIX}" for n in LAYER_METRICS} <= {m["name"]
                                                    for m in metrics}
    for m in metrics:
        assert m["moves"] == "tokens_per_s" and CELL in m["workloads"]
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert (spec["layer"], spec["unit"]) == (m["layer"], m["unit"])


def test_work_counts_against_hand_worked_numbers():
    m = dict(CONFIG)
    # 9 Mamba layers of 76,182,976, one attention layer of 60,821,504, a
    # quarter of the tied vocabulary and the final norm
    assert work_mamba.parameters(m) == 797_850_560 == (
        9 * 76_182_976 + 60_821_504 + 25_088 * 2_048 + 2_048)
    assert work_mamba.layer_counts(m) == (9, 1)
    # the scan a token forward, in chunks of 256: 64 heads of
    # 2 Q P + 4 N P, and G = C B^T once, 2 Q N
    ssd = work_mamba.ssd_scan(m, 1, 4096)
    assert ssd["flops"] == 3 * 4096 * (64 * (2 * 256 * 64 + 4 * 128 * 64)
                                       + 2 * 256 * 128)
    assert ssd["flops"] // (3 * 4096) == 4_259_840
    assert ssd["bytes"] == 2 * 4096 * (2 * (2 * 4096 + 2 * 128) + 4 * 2 * 64)
    attn = work_mamba.gqa_attention(m, 1, 4096)
    assert attn["flops"] == 6 * 2 * 32 * 4096 * 4097 // 2 * 64
    # a token forward: 9 Mamba layers of 156,600,320, the attention layer's
    # 138,416,128 (its scores at 4,096 tokens 16,781,312), the head's
    # 102,760,448
    step = work_mamba.mamba_train_step(m, 1, 4096)
    per_token = 9 * (34_865_152 + 16_777_216 + 34_816 + 4_259_840
                     + 100_663_296) + (20_971_520 + 16_781_312
                                       + 100_663_296) + 102_760_448
    assert per_token == 1_650_579_456
    assert step == {"flops": 3 * 4096 * per_token, "tokens": 4096}


def _run(trace=None, counters=None):
    outcome = types.SimpleNamespace(counters=counters or {})
    return {"trace": trace, "outcome": outcome,
            "peak": {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}}


def test_the_readers_read_nothing_where_there_is_nothing():
    sizes = {"sizes": dict(CONFIG), "batch": 1, "sequence": 4096,
             "calls_in_trace": 10, "step_flops": 1.0}
    without_ssd = {"op_seconds": {"jit_step:fusion.1": 0.5},
                   "module_seconds": {"jit_step": 1.0}, "scope_seconds": {},
                   "busy_s": 1.0, "window_s": 2.0, "module_gaps_s": []}
    for run in (_run(), _run(without_ssd), _run(without_ssd, sizes)):
        assert mamba.kernel_roofline(run, "ssd_", "ssd_scan", "mamba") is None
        assert mamba.kernel_roofline(run, "attn_flash", "gqa_attention",
                                     "attention") is None
        assert train.op_share_of_step(run, "ssd_", "jit_step") is None
        assert train.scope_share_of_step(run, ["ssm.scan"], "jit_step") is None
    assert device.step_mfu(_run()) is None
    assert device.idle_share(_run()) is None
    # a trace that holds the kernels: the least time over their time
    with_ssd = dict(without_ssd, op_seconds={"jit_step:ssd_chunk_fwd_1": 0.03,
                                             "jit_step:ssd_chunk_bwd_1": 0.07})
    least = 10 * 9 * max(work_mamba.ssd_scan(CONFIG, 1, 4096)["flops"]
                         / 1.97e14,
                         work_mamba.ssd_scan(CONFIG, 1, 4096)["bytes"]
                         / 8.19e11)
    assert abs(mamba.kernel_roofline(_run(with_ssd, sizes), "ssd_",
                                     "ssd_scan", "mamba")
               - 100 * least / 0.1) < 1e-9


def test_the_dry_run_step_names_the_mamba_scopes():
    """The cell's step as its dry run compiles it (the driver's own
    configuration from the file's dry-run sizes, the reference's weights):
    every scope of the Mamba-2 layer, the attention layer, the MLP and what
    every model writes is in the compiled step's table, which a traced run
    hands to the reduction."""
    import jax
    import jax.numpy as jnp
    import optax

    import harness
    import reference_mamba
    import trace_reduce
    from drivers import train_mamba_step

    sys.path.insert(0, ROOT)
    from brpc_tpu.models import mamba as model

    ctx = harness.Context(cell={}, config=CONFIG, traffic={}, seed=0,
                          seconds=1, trace=True, dry=True, control=None,
                          t_process=0.0)
    m = {k: ctx.size(k) for k in train_mamba_step._SIZES}
    cfg = train_mamba_step.model_config(model, m)
    optimizer = optax.adamw(1e-4)
    params = jax.eval_shape(lambda k: reference_mamba.mamba_init(k, m),
                            jax.random.PRNGKey(0))
    step = jax.jit(model.make_train_step(cfg, optimizer)).lower(
        params, jax.eval_shape(optimizer.init, params),
        jax.ShapeDtypeStruct((1, 64), jnp.int32)).compile()
    tables = trace_reduce.op_scopes(step.as_text())
    assert SSM_SCOPES <= {scope for table in tables.values()
                          for scope, _ in table.values()}
