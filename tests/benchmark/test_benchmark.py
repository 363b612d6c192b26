"""The benchmark's own tests: the manifest resolves, the yardstick's
arithmetic is right, a dry run of every driver prints the contract's last
line, and the comparison that decides ``correct`` fails what it has to
fail: the lower-precision control and each planted fault.

Everything runs on the CPU at the tiny sizes of the files' ``dry_run``
tables (the embedding shard on the fake PJRT plug-in).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import gen  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import run as bench_run  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(BENCH, "candidates.json")) as _f:
    CANDIDATES = json.load(_f)      # cells not yet held to a bound
MANIFESTS = [MANIFEST, CANDIDATES]
CELLS = [w["name"] for m in MANIFESTS for w in m["workloads"]]
TRAIN_CELL = "train_mistral7b_1chip.steady"
TRAIN_MIX = "ps_sarvam105b_1chip.train_mix"
# The train cells, and scopes of the program that each one's compiled step
# names (on the CPU attention is the dense form; "-": under no scope).
_MOE = {"moe.router", "moe.sort", "moe.experts", "moe.combine", "moe.shared"}
TRAIN_SCOPES = {
    TRAIN_CELL: {"attn.dense", "-"},
    "train_kanana2_30b_a3b_1chip.seq8k": _MOE | {
        "mla.q_proj", "mla.kv_down", "mla.kv_up", "mla.out_proj", "-"},
    "train_ouro_2_6b_1chip.loop4_2x4k": {
        "loop.layer.attn", "loop.layer.mlp", "loop.pass_norm",
        "loop.exit_gate", "loop.head", "loop.head/loop.exit_loss", "-"},
    "train_qwen3_next_80b_a3b_1chip.lin3full1_8k": _MOE | {
        "gdn.in_proj", "gdn.conv", "gdn.rule", "gdn.out", "gattn.qkv",
        "gattn.out", "-"},
}


# -- the manifest ---------------------------------------------------------------

@pytest.mark.parametrize("MANIFEST", MANIFESTS, ids=["held", "candidates"])
def test_manifest_files_resolve(MANIFEST):
    for c in MANIFEST["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert os.path.exists(os.path.join(
            BENCH, "drivers", config["driver"] + ".py"))
        assert c["file"].startswith("benchmark/configs/")
    for w in MANIFEST["workloads"]:
        assert os.path.exists(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
        assert w["config"] in {c["name"] for c in MANIFEST["configs"]}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


@pytest.mark.parametrize("MANIFEST", MANIFESTS, ids=["held", "candidates"])
def test_every_layer_metric_moves_what_its_cells_report(MANIFEST):
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    cells_here = [w["name"] for w in MANIFEST["workloads"]]
    for m in MANIFEST["per_layer"]:
        moved = e2e[m["moves"]]
        cells = m.get("workloads", cells_here)
        assert set(cells) <= set(moved.get("workloads", cells_here)), m["name"]
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        module, function = spec["reader"].rsplit(".", 1)
        mod = __import__("readers." + module, fromlist=[function])
        assert callable(getattr(mod, function)), m["name"]
        for key in ("unit", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)


@pytest.mark.parametrize("cell", sorted(TRAIN_SCOPES))
def test_a_train_cell_s_layer_metrics_resolve_and_two_read_the_scopes(cell):
    """Every ``per_layer`` entry of a train cell has its ``layer_metrics/``
    file and reader, whatever later PRs append; among them the optimizer's
    share of the step and the share under no scope of the program."""
    mix = cell.rsplit(".", 1)[1]
    e2e = {m["name"] for m in bench_run.metrics_of(MANIFEST, "end_to_end",
                                                   cell)}
    assert e2e == {"tokens_per_s", "setup_s"}
    metrics = bench_run.metrics_of(MANIFEST, "per_layer", cell, e2e)
    assert {f"{n}.{mix}" for n in (
        "step_mfu", "device_idle_share", "step_gap_ms_p50", "opt_step_share",
        "unscoped_step_share")} <= {m["name"] for m in metrics}
    for m in metrics:
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        module, function = spec["reader"].rsplit(".", 1)
        mod = __import__("readers." + module, fromlist=[function])
        assert callable(getattr(mod, function)), m["name"]
        assert spec["workloads"] == m["workloads"] == [cell], m["name"]
        if spec["reader"] == "train.scope_share_of_step":
            assert spec["args"]["program"] == "jit_step" and \
                spec["args"]["scopes"] and m["unit"] == "%", m["name"]


def test_every_held_cell_reports_setup_another_metric_and_a_layer_metric():
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    for cell in (w["name"] for w in MANIFEST["workloads"]):
        e2e = bench_run.metrics_of(MANIFEST, "end_to_end", cell)
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2, cell
        assert bench_run.metrics_of(MANIFEST, "per_layer", cell, names), cell


def test_catalog_keys_are_kept():
    """sarvam-105b is in the model-configs catalog: the file holds its
    config whole, and only ``reduced`` keys may differ."""
    with open(os.path.join(BENCH, "configs", "ps_sarvam105b_1chip.json")) as f:
        c = json.load(f)
    assert (c["vocab_size"], c["hidden_size"]) == (262144, 4096)
    assert c["vocab_size"] * c["hidden_size"] * 4 == 2 ** 32
    assert c["num_experts_per_tok"] == 8 and c["kv_lora_rank"] == 512


# -- the generator ---------------------------------------------------------------

def test_gen_is_deterministic_in_the_seed():
    big = 2 ** 31 + 12345
    a = gen.ZipfIds(4096, 1.1, big, stripes=2).draw(gen.rng_for(big, 4, 0), 500)
    b = gen.ZipfIds(4096, 1.1, big, stripes=2).draw(gen.rng_for(big, 4, 0), 500)
    c = gen.ZipfIds(4096, 1.1, big + 1, stripes=2).draw(
        gen.rng_for(big + 1, 4, 0), 500)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 4096
    t1 = gen.normal_table(big, 64, 16, threads=1)
    t2 = gen.normal_table(big, 64, 16, threads=4)
    assert np.array_equal(t1, t2) and t1.dtype == np.float32


def test_zipf_stripes_load_every_seed_alike():
    shares = []
    for seed in (1, 2, 3):
        ids = gen.ZipfIds(4096, 1.1, seed, stripes=2).draw(
            gen.rng_for(seed, 9), 20000)
        shares.append((ids < 2048).mean())
    assert max(shares) - min(shares) < 0.02
    assert 0.5 < shares[0] < 0.65         # rank 1 lives in the first range


def test_poisson_due_times():
    due = gen.poisson_due_times(gen.rng_for(7, 1), 200.0, 10.0)
    assert (np.diff(due) > 0).all() and due[-1] < 10.0
    assert abs(due.size - 2000) < 5 * 2000 ** 0.5


# -- work, percentiles, the ledger -------------------------------------------------

def test_work_counts_against_hand_worked_numbers():
    assert work.gather_rows(64, 4096) == {
        "flops": 0, "bytes": 64 * 4 + 2 * 64 * 16384}
    assert work.scatter_sub(2048, 4096)["bytes"] == 2048 * 4 + 3 * 2048 * 16384
    m = {"hidden_size": 4096, "intermediate_size": 14336, "vocab_size": 32000,
         "num_hidden_layers": 2, "num_attention_heads": 32,
         "num_key_value_heads": 8, "head_dim": 128}
    layer = 2 * 2048 * (4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336)
    attn = 2 * 2048 * 2048 * 4096
    head = 2 * 2048 * 4096 * 32000
    assert work.llama_train_step(m, 1, 2048)["flops"] == \
        3 * (2 * (layer + attn) + head)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.roofline_seconds({"flops": 197e12, "bytes": 0}, peak) == 1.0
    assert work.roofline_seconds({"flops": 0, "bytes": 819e9 * 2}, peak) == 2.0
    assert work.all_reduce(2 ** 31, 4)["bus_bytes"] == 3 * 2 ** 30


def test_percentile_is_nearest_rank():
    assert harness.percentile(range(1, 101), 95) == 95
    assert harness.percentile([5.0], 95) == 5.0
    assert harness.percentile([1, 2, 3, 4], 50) == 2


def test_row_ledger_bounds_any_order_and_catches_a_lost_update():
    rng = np.random.default_rng(0)
    table = rng.standard_normal((64, 8)).astype(np.float32)
    ids = rng.integers(0, 16, 200).astype(np.int32)
    grads = rng.standard_normal((200, 8)).astype(np.float32)
    ledger = reference.RowLedger(table, np.arange(16))
    ledger.apply(ids, grads, 0.1)
    other = table.copy()
    for j in rng.permutation(200):          # another order of the same sums
        other[ids[j]] -= np.float32(0.1) * grads[j]
    assert ledger.gap(np.arange(16), other[:16]) <= 2.0
    lost = table.copy()
    np.subtract.at(lost, ids[:100], np.float32(0.1) * grads[:100])
    assert ledger.gap(np.arange(16), lost[:16]) > 1e3
    assert ledger.gap(np.arange(16), reference.to_bfloat16(
        ledger.rows)) > 1e2


# -- the trace reducer -------------------------------------------------------------

def test_trace_reduce_on_the_recorded_trace():
    """Recorded on a v5e (PR 25 probe): two 131,072-row shards, four
    threads of lookup + apply for six seconds."""
    r = trace_reduce.reduce(os.path.join(
        os.path.dirname(__file__), "data", "ps_probe.xplane.pb"), 1)
    assert r["module_runs"] == {"brt_gather_rows": 24, "brt_scatter_sub": 17}
    assert 0 < r["busy_s"] < r["window_s"]
    top, seconds = r["breakdown"]["device_ops"][0]
    assert top == "brt_scatter_sub:copy.3_f32[131072,4096]"
    assert 0.10 < seconds < 0.12 and len(r["breakdown"]["device_ops"]) <= 10
    # no operation of these programs contains another: self time is the time
    assert r["op_self_seconds"] == pytest.approx(r["op_seconds"])
    assert sum(r["op_self_seconds"].values()) == pytest.approx(r["busy_s"])


# Recorded lines of three compiled steps, and what the program wrote each
# instruction under: a fusion with no metadata of its own through two nested
# fused computations to its siblings' scope, a kernel behind a ``cond`` that
# repeats the path, forward / recomputed / backward, a scope around the
# differentiated call (``jvp(loop.head)``), an einsum's spec, XLA's own
# shortened and joined names, a ``while``, and a copy the compiler put in.
_SCOPES = [
    ("fusion.18", ("moe.router", "bwd")),
    ("fusion.597", ("moe.router", "bwd")),
    ("gdn_chunk_prep.11", ("gdn.rule/gdn.chunk_prep/gdn_chunk_prep", "fwd")),
    ("convolution.149", ("mla.q_proj", "fwd")),
    ("convolution.163.clone.1", ("mla.q_proj", "bwd")),
    ("slice.898", ("mla.kv_down", "remat")),
    ("moe_gmm_fwd.70", ("moe.experts/moe_gmm_fwd", "remat")),
    ("attn_flash_fwd.14", ("attn.flash_fwd/attn_flash_fwd", "fwd")),
    ("iota.282", ("moe.sort", "outside")),
    ("broadcast_multiply_fusion.2", ("-", "outside")),
    ("while.155", ("-", "fwd")),
    ("reduce_sum.497", ("mla.kv_down", "outside")),
    ("reshape.158", ("-", "fwd")),
    ("ne.53", ("loop.head/loop.exit_loss", "remat")),
    ("broadcast.1207", ("loop.exit_gate", "bwd")),
    ("attn_flash_bwd.8", ("loop.layer.attn/attn.flash_bwd/attn_flash_bwd",
                          "bwd")),
    ("copy.800", ("-", "none")),
    ("scatter.8", ("-", "none")),
]


@pytest.fixture(scope="module")
def recorded_scopes():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "step_hlo_lines.txt")) as f:
        (program, table), = trace_reduce.op_scopes(f.read()).items()
    assert program == "jit_step"
    return table


@pytest.mark.parametrize("instruction,want", _SCOPES,
                         ids=[name for name, _ in _SCOPES])
def test_the_scope_rule_on_recorded_hlo_lines(recorded_scopes, instruction,
                                              want):
    assert tuple(recorded_scopes[instruction]) == want


def test_scope_of_takes_jax_s_own_components_out():
    scope_of = trace_reduce.scope_of
    assert scope_of("jit(f)/jvp()/while/body/closed_call/mla.q_proj/"
                    "dot_general") == ("mla.q_proj", "fwd")
    assert scope_of("jit(f)/transpose(jvp())/while/body/closed_call/"
                    "checkpoint/rematted_computation/mla.q_proj/tanh") == (
        "mla.q_proj", "remat")
    assert scope_of("jit(f)/opt.update/sub") == ("opt.update", "outside")
    assert scope_of("jit(f)/jvp(a/b)/vmap(c)/custom_jvp_call/d/mul") == (
        "a/b/c/d", "fwd")
    assert scope_of("jit(f)/jvp(jit(take_along_axis))/gather") == ("-", "fwd")
    assert scope_of("reduce_sum") == scope_of("") == ("-", "outside")


def test_self_time_of_a_while_is_what_its_body_leaves():
    """A ``while`` around three ops and a gap, one op after it: the self
    times add up to the union, a loop keeps only its own microseconds."""
    ops = [("%while.7 = (s32[]) while(...)", 0.0, 10.0),
           ("%a = f32[8]{0} fusion(...)", 1.0, 3.0),
           ("%b = f32[8]{0} fusion(...)", 3.0, 4.0),
           ("%c = f32[8]{0} fusion(...)", 6.0, 9.5),
           ("%d = f32[8]{0} fusion(...)", 12.0, 13.0)]
    own = trace_reduce.self_seconds(ops)
    assert own == [3.5, 2.0, 1.0, 3.5, 1.0]
    assert sum(own) == trace_reduce.union_seconds(
        [(s, e) for _, s, e in ops]) == 11.0
    # events that overlap without nesting: the one started last has the time
    assert trace_reduce.self_seconds(
        [("x", 0.0, 5.0), ("y", 3.0, 8.0), ("z", 4.0, 4.5)]) == [3.0, 4.5, 0.5]


_STEP_OPS = [
    ("%while.7 = (s32[]{:T(128)}, f32[8,8]{1,0}) while(...)", 0.0, 10.0),
    ("%fusion.1 = f32[8,8]{1,0} fusion(...)", 1.0, 3.0),
    ("%attn_flash_fwd.2 = bf16[8,8]{1,0} custom-call(...)", 3.0, 4.0),
    ("%fusion.3 = f32[8,8]{1,0} fusion(...)", 6.0, 9.5),
    ("%fusion.4 = f32[8]{0} fusion(...)", 12.0, 13.0),
    ("%copy.5 = f32[8]{0} copy(...)", 13.0, 13.5)]
_STEP_TABLE = {"jit_step": {
    "while.7": ["-", "fwd"], "fusion.1": ["mla.q_proj", "fwd"],
    "attn_flash_fwd.2": ["attn.flash_fwd/attn_flash_fwd", "fwd"],
    "fusion.3": ["mla.q_proj", "bwd"], "fusion.4": ["-", "outside"]}}


def _reduced_step(scopes):
    devices = {0: {"modules": [("jit_step(9)", 0.0, 14.0)],
                   "ops": list(_STEP_OPS)}}
    return trace_reduce.reduce_planes(devices, [], window=(0.0, 14.0),
                                      scopes=scopes)


def test_device_time_by_scope_and_the_breakdown_without_a_while_row():
    r = _reduced_step(_STEP_TABLE)
    assert r["op_seconds"]["jit_step:while.7_s32[]"] == 10.0     # as it was
    assert r["op_self_seconds"]["jit_step:while.7_s32[]"] == 3.5
    assert sum(r["op_self_seconds"].values()) == r["busy_s"] == 11.5
    assert r["scope_seconds"] == {"jit_step": {
        "-": {"fwd": 3.5, "outside": 1.0, "none": 0.5},
        "mla.q_proj": {"fwd": 2.0, "bwd": 3.5},
        "attn.flash_fwd/attn_flash_fwd": {"fwd": 1.0}}}
    assert sum(s for by_phase in r["scope_seconds"]["jit_step"].values()
               for s in by_phase.values()) == r["busy_s"]
    assert r["breakdown"]["device_ops"] == [
        ["jit_step:mla.q_proj", 5.5], ["jit_step:-/while.7_s32[]", 3.5],
        ["jit_step:attn.flash_fwd/attn_flash_fwd", 1.0],
        ["jit_step:-/fusion.4_f32[8]", 1.0],
        ["jit_step:-/copy.5_f32[8]", 0.5]]
    # on the chip a loop's own time is microseconds: no row of the ten
    quick = [(n, s, e) if "while" not in n else (n, 1.0, 9.5)
             for n, s, e in _STEP_OPS]
    q = trace_reduce.reduce_planes(
        {0: {"modules": [("jit_step(9)", 0.0, 14.0)], "ops": quick}}, [],
        window=(0.0, 14.0), scopes=_STEP_TABLE)
    assert q["op_self_seconds"]["jit_step:while.7_s32[]"] == 2.0
    # without a table: what it returned before, and the new self times
    plain = _reduced_step(None)
    assert "scope_seconds" not in plain
    assert plain["breakdown"]["device_ops"][0] == [
        "jit_step:while.7_s32[]", 10.0]
    assert {k: plain[k] for k in ("op_seconds", "busy_s", "module_seconds",
                                  "module_gaps_s")} == {
        k: r[k] for k in ("op_seconds", "busy_s", "module_seconds",
                          "module_gaps_s")}


def test_a_program_without_a_table_keeps_its_ops_in_the_breakdown():
    """The collective's one op has no scope and needs none: a table of
    another program, or none, leaves its row as it was."""
    devices = {i: {"modules": [("jit_all_reduce(3)", 0.0, 5.0)],
                   "ops": [("%psum.7 = f32[536870912]{0} all-reduce(...)",
                            0.0, 5.0)]} for i in range(4)}
    for scopes in (None, _STEP_TABLE):
        r = trace_reduce.reduce_planes(devices, [], window=(0.0, 5.2),
                                       scopes=scopes)
        assert r["breakdown"]["device_ops"] == [
            ["jit_all_reduce:psum.7_f32[536870912]", 20.0]]
        assert r["op_self_seconds"] == r["op_seconds"]
        assert r["busy_s"] == 5.0


def test_scope_share_of_step_on_a_hand_made_trace():
    from readers import train
    run = {"trace": _reduced_step(_STEP_TABLE)}
    share = lambda *a, **k: train.scope_share_of_step(  # noqa: E731
        run, *a, program="jit_step", **k)
    assert share(["mla."]) == pytest.approx(100 * 5.5 / 14.0)
    assert share(["mla.q_proj"], phases=["bwd"]) == pytest.approx(25.0)
    assert share(["attn.flash", "mla.kv_up"]) == pytest.approx(100 / 14.0)
    assert share(["attn_flash_fwd"]) == pytest.approx(100 / 14.0)  # 2nd part
    assert share(["-"]) == pytest.approx(100 * 5.0 / 14.0)
    assert share(["opt.update", "-"], phases=["outside"]) == \
        pytest.approx(100 / 14.0)
    # nothing to read: no such scope, no table, no trace, another program
    assert share(["gdn."]) is None
    assert train.scope_share_of_step({"trace": _reduced_step(None)}, ["-"],
                                     "jit_step") is None
    assert train.scope_share_of_step({"trace": None}, ["-"],
                                     "jit_step") is None
    assert train.scope_share_of_step(run, ["-"], "jit_other") is None


def test_trace_reduce_names_gaps_by_host_span():
    devices = {0: {"modules": [("p(1)", 1.0, 2.0), ("p(1)", 5.0, 6.0)],
                   "ops": [("%fusion = f32[8,8]{1,0} fusion(...)", 1.0, 1.5),
                           ("%copy.1 = f32[8,8]{1,0} copy(...)", 5.0, 6.0)]}}
    r = trace_reduce.reduce_planes(
        devices, [("fetch", 2.0, 4.5), ("stage", 4.5, 5.0)], window=(0.0, 8.0))
    assert r["busy_s"] == 1.5 and r["window_s"] == 8.0
    assert r["op_seconds"] == {"p:fusion_f32[8,8]": 0.5, "p:copy.1_f32[8,8]": 1.0}
    assert dict(map(tuple, r["breakdown"]["idle_gaps"])) == {
        "fetch": 3.0, "no_span": 3.0}
    assert r["module_gaps_s"] == [3.0]
    assert trace_reduce.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4


# -- a dry run of every driver -------------------------------------------------------

def _dry(cell, *extra):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(2 ** 31 + 7), "--seconds", "1.5", "--cpu-dry-run",
         *extra], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_dry_run_prints_the_last_line(cell, trace):
    out, err = _dry(cell, "--trace", trace)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"] and list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"]
    assert out["device"]["platform"] == "cpu" and out["metrics"] == {}
    assert out["checks"] and all(
        c["name"] in err for c in out["checks"])       # stderr's last lines
    if cell in TRAIN_SCOPES:
        # A traced run hands the compiled step's scopes to the reduction,
        # with or without a chip; on a CPU there is no device trace, so no
        # share of the step is among what the run would report.
        assert ("scopes" in out["dry_run"]) == (trace == "1")
        if trace == "1":
            assert TRAIN_SCOPES[cell] <= set(out["dry_run"]["scopes"])
            by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
            assert all(by_name[n]["source"] == "program_counter"
                       for n in out["dry_run"]["would_report"])


@pytest.mark.parametrize("cell", [TRAIN_MIX, TRAIN_CELL])
def test_the_lower_precision_control_is_not_correct(cell):
    out, _ = _dry(cell, "--control", "lowprec")
    assert out["correct"] is False


def test_no_chip_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         TRAIN_CELL, "--seed", "1", "--seconds", "1"], capture_output=True,
        text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 3 and proc.stdout.strip() == ""


# -- the timed path broken underneath ---------------------------------------------------

def _run_in_process(cell, capsys):
    rc = bench_run.main(["--workload", cell, "--seed", "77", "--seconds",
                         "1.5", "--cpu-dry-run"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _ps_fault(monkeypatch, fault):
    from brpc_tpu.ps_remote import DevicePsShardServer as cls
    real_apply, real_serve = cls._apply_batch, cls._serve
    if fault == "state_unchanged":
        monkeypatch.setattr(cls, "_apply_batch",
                            lambda self, ids, grads, metas=(): None)
    elif fault == "half_batch":
        monkeypatch.setattr(
            cls, "_apply_batch", lambda self, ids, grads, metas=():
            real_apply(self, ids[:len(ids) // 2], grads[:len(ids) // 2],
                       metas=metas))
    elif fault == "answer_altered":
        def serve(self, method, payload, deadline_us=0):
            out = real_serve(self, method, payload, deadline_us)
            if method == "Lookup":
                rows = np.frombuffer(bytes(out), np.float32).copy()
                rows[3] += np.float32(1e-3)
                return rows.tobytes()
            return out
        monkeypatch.setattr(cls, "_serve", serve)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_ps_fault_comes_out_not_correct(fault, monkeypatch, capsys):
    _ps_fault(monkeypatch, fault)
    out = _run_in_process(TRAIN_MIX, capsys)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_comes_out_not_correct(fault, monkeypatch, capsys):
    from brpc_tpu.models import llama
    real = llama.make_train_step

    def broken(cfg, optimizer, attn_fn=None):
        step = real(cfg, optimizer, attn_fn)
        if fault == "state_unchanged":
            return lambda p, s, t: (p, s, step(p, s, t)[2])
        return lambda p, s, t: step(p, s, t[:, :t.shape[1] // 2])
    monkeypatch.setattr(llama, "make_train_step", broken)
    out = _run_in_process(TRAIN_CELL, capsys)
    assert out["correct"] is False, out["checks"]


def test_sound_runs_in_process_are_correct(capsys):
    assert _run_in_process(TRAIN_MIX, capsys)["correct"] is True


COLLECTIVE = [c for c in CELLS if c.startswith("collective_")]


@pytest.mark.parametrize("cell", COLLECTIVE)
def test_collective_without_its_exchange_is_not_correct(cell, monkeypatch,
                                                        capsys):
    from brpc_tpu.parallel import collective_channel
    monkeypatch.setattr(collective_channel.lax, "psum", lambda x, axis: x)
    out = _run_in_process(cell, capsys)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", COLLECTIVE)
def test_collective_lower_precision_control_is_not_correct(cell):
    out, _ = _dry(cell, "--control", "lowprec")
    assert out["correct"] is False
