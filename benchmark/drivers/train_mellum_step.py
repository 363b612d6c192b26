"""Driver of the Mellum2 training cell: the normal train step of
``brpc_tpu/models/windowed.py`` — the file the Laguna-XS.2 cell runs through
— laid out from Mellum2's per-layer tables (three sliding-window layers and
the full layer that ends their period, no leading dense layer, one head
count on every layer, q/k norms, yarn on the whole head, no output gate,
softmax-routed experts of which this chip holds a quarter and no shared one;
bf16 compute, float32 master weights, AdamW, donated state) at the published
configuration's widths.

The shape of ``train_windowed_step.py``: set-up builds ONE object, the
compiled step with its state, makes the weights on the device from the seed
in one jitted call (``reference_mellum.mellum_init``), drives the object
through its first steps and hands the same object to the window; the
comparison follows those steps with the plain float32 reference (a mask of
compared positions, dense attention a head at a time) once the window has
closed and the state is freed. Beside the loss the step returns ``stats`` an
expert layer; nothing reads them inside the window.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import harness
import reference
import reference_mellum
import trace_reduce
import work_mellum
from drivers.train_looped_step import leaf_gap
from drivers.train_moe_step import _agreement

N_BATCHES = 64           # distinct token batches, cycled through the window

_SIZES = ("hidden_size", "vocab_size", "num_hidden_layers",
          "intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim",
          "sliding_window", "rope_parameters", "layer_types",
          "mlp_layer_types", "num_experts", "router_experts", "expert_offset",
          "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob",
          "rms_norm_eps")
_KINDS = {"full_attention": "full", "sliding_attention": "window"}


def model_config(windowed, m: dict):
    """The program's configuration from the published names: each switch of
    ``WindowedConfig`` by what the file states (no gating key: no gate; no
    shared_expert_intermediate_size: no shared expert; norm_topk_prob: the
    softmax router; a rope group without partial_rotary_factor: the whole
    head)."""
    full, window = (m["rope_parameters"][k]
                    for k in ("full_attention", "sliding_attention"))
    if not m["norm_topk_prob"]:
        raise SystemExit("benchmark: the softmax router renormalises its "
                         "chosen shares; norm_topk_prob false is not built")
    return windowed.WindowedConfig(
        vocab_size=m["vocab_size"], hidden=m["hidden_size"],
        n_layers=m["num_hidden_layers"],
        layer_types=tuple(_KINDS[t] for t in m["layer_types"]),
        mlp_layer_types=tuple(m["mlp_layer_types"]),
        full_heads=m["num_attention_heads"],
        window_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        window=m["sliding_window"], attn_gate=False, qk_norm=True,
        window_rope_theta=window["rope_theta"],
        full_rope_theta=full["rope_theta"],
        full_rotary_factor=full.get("partial_rotary_factor", 1.0),
        yarn_factor=full["factor"],
        yarn_original_positions=full["original_max_position_embeddings"],
        yarn_beta_fast=full["beta_fast"], yarn_beta_slow=full["beta_slow"],
        yarn_attention_factor=full["attention_factor"],
        intermediate=m["intermediate_size"], n_experts=m["router_experts"],
        experts_per_token=m["num_experts_per_tok"],
        moe_intermediate=m["moe_intermediate_size"], shared_intermediate=0,
        router="softmax", norm_eps=m["rms_norm_eps"],
        n_held=m["num_experts"], expert_offset=m["expert_offset"])


def _matrices(m: dict) -> list:
    """The leaves of the weights' tree that hold a projection's worth of
    numbers and more (w_k's, the smallest), by the leaf's name."""
    import jax

    tree = jax.eval_shape(lambda k: reference_mellum.mellum_init(k, m),
                          jax.random.PRNGKey(0))
    least = m["hidden_size"] * m["num_key_value_heads"] * m["head_dim"]
    return [jax.tree_util.keystr(path) for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]
            if leaf.size >= least]


def _compare(ctx, got: dict, want: dict, m: dict) -> None:
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        print(f"benchmark: loss step {i + 1}: {a!r} (reference {b!r})",
              file=sys.stderr)
        name = f"loss_gap_step{i + 1}"
        if name in ctx.size("limits"):
            ctx.check(name, abs(a - b) / abs(b))
    # Every leaf against its own reference norm, and the matrices alone
    # under a limit that a fault of a few percent of one layer cannot pass.
    ctx.check("grad_norm_gap", leaf_gap(
        got["grad_norms"], want["grad_norms"], "gradient gap"))
    matrices = _matrices(m)
    ctx.check("matrix_grad_norm_gap", leaf_gap(
        {k: got["grad_norms"][k] for k in matrices},
        {k: want["grad_norms"][k] for k in matrices},
        "matrix gradient gap"))
    ctx.check("delta_norm_gap", leaf_gap(
        got["delta_norms"], want["delta_norms"], "change gap"))
    ctx.check("routing_disagreement",
              1.0 - _agreement(got["selected"], want["selected"]))


def run(ctx: harness.Context) -> harness.Outcome:
    # First of all, before any device is touched: a checkout whose model
    # file cannot be laid out from per-layer tables fails here, at once.
    sys.path.insert(0, harness.ROOT)
    from brpc_tpu.models import windowed
    from brpc_tpu.ops import grouped_matmul as gm
    from brpc_tpu.ops.flash_attention import band_calls, band_tiles

    m = {k: ctx.size(k) for k in _SIZES}
    cfg = model_config(windowed, m)

    import jax
    import optax

    devices = harness.jax_devices(ctx.cell["chips"], ctx.dry)
    ctx.lap("jax_devices")

    o = ctx.config["optimizer"]
    batch, seq = ctx.mix("batch"), ctx.mix("sequence")
    steps_followed = ctx.config["reference_steps"]
    tokens = reference.token_batches(ctx.seed, N_BATCHES, batch, seq,
                                     m["vocab_size"])

    if ctx.control:
        # The reference in the program's place: a lower precision, or a
        # planted fault. No program, no window.
        variants = {
            "lowprec": {"matmul_in": reference.fp8_operand},
            "half_batch": {"keep": (seq - 1) // 2},
            **{fault: {"fault": fault} for fault in reference_mellum.FAULTS}}
        want = reference_mellum.train_reference(ctx.seed, m, o, tokens,
                                                steps_followed)
        got = reference_mellum.train_reference(ctx.seed, m, o, tokens,
                                               steps_followed,
                                               **variants[ctx.control])
        _compare(ctx, got, want, m)
        ctx.check("dropped_assignments", 0.0)
        return harness.Outcome(
            end_to_end={}, attempted=steps_followed, failed=0,
            setup_s=time.monotonic() - ctx.t_process,
            device=harness.device_report(devices, 1))

    optimizer = optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                            eps=o["eps"], weight_decay=o["weight_decay"])
    key = reference.seed_key(ctx.seed)
    params = jax.jit(lambda k: reference_mellum.mellum_init(k, m))(key)
    opt_state = jax.jit(optimizer.init)(params)
    jax.block_until_ready(opt_state)
    ctx.lap("weights_from_seed")
    traced = jax.jit(windowed.make_train_step(cfg, optimizer),
                     donate_argnums=(0, 1)).trace(
                         params, opt_state, tokens[0])
    step = traced.lower().compile()
    # the band kernels' calls in the step as traced, by the tiles each runs
    # with; none where attention runs in its dense form
    calls = band_calls(traced.jaxpr.jaxpr)
    del traced
    ctx.lap("compile_step")
    first_moment = jax.jit(lambda s: reference.leaf_norms(s[0].mu))
    change = jax.jit(lambda p, k: reference.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, reference_mellum.mellum_init(k, m))))

    state = [params, opt_state, 0]      # the one object: set-up's and the window's
    del params, opt_state
    stats_log = []                      # device arrays; read after the window
    got = {"losses": []}

    def one_step():
        state[0], state[1], loss, stats = step(
            state[0], state[1], tokens[state[2] % N_BATCHES])
        if state[2] == 0:
            got["selected"] = stats["selected"]
        stats_log.append({k: v for k, v in stats.items() if k != "selected"})
        state[2] += 1
        return loss

    # The object's first steps, through the window's own call and feed.
    for i in range(steps_followed):
        got["losses"].append(float(one_step()))
        if i == 0:      # mu_1 = (1 - b1) * g_1: the gradient as Adam got it
            got["grad_norms"] = {k: float(v) / (1 - o["b1"]) for k, v in
                                 first_moment(state[1]).items()}
    got["delta_norms"] = {k: float(v) for k, v in change(state[0], key).items()}
    got["selected"] = np.asarray(got["selected"])
    jax.block_until_ready(state[0])
    ctx.lap("first_steps")

    # -- the window: back to back, at most two steps in flight ------------
    window = harness.TracedWindow(ctx.trace and not ctx.dry)
    window.start()
    setup_s = time.monotonic() - ctx.t_process
    n_before = len(stats_log)
    steps, steps_in_trace, elapsed, loss = harness.back_to_back(
        one_step, ctx.seconds, 2, window,
        min(ctx.seconds, ctx.mix("trace_seconds", ctx.seconds)), ctx.spans)
    jax.block_until_ready(state[0])
    last_loss = float(loss)
    ctx.lap("window")
    device = harness.device_report(devices, 1)
    # Traced runs only, and the window closed: the scope of the program
    # that each instruction of the compiled step was written under.
    op_scopes = trace_reduce.op_scopes(step.as_text()) if ctx.trace else None
    stats = {k: np.stack([np.asarray(s[k]) for s in stats_log])
             for k in stats_log[0]}                     # each [steps, L]
    dropped = int(stats["dropped"].sum())       # the first steps' too
    stats = {k: v[n_before:] for k, v in stats.items()}     # the window's
    del state, step, stats_log

    want = reference_mellum.train_reference(ctx.seed, m, o, tokens,
                                            steps_followed)
    _compare(ctx, got, want, m)
    ctx.lap("reference")
    ctx.check("dropped_assignments", float(dropped))
    ctx.check("last_loss_not_finite", 0.0 if np.isfinite(last_loss) else 1.0,
              0.0)
    routed = stats["routed"]
    in_trace = routed[:steps_in_trace] if steps_in_trace else routed
    # what the band kernels' loops visit at this sequence, by their own
    # arithmetic over the tiles the step's own calls run with, against the
    # pairs the window shows
    band = None
    if calls:       # one forward and one backward call, whatever the layer
        assert len(calls) == 2, calls
        tiles = {name: pair for name, *pair in calls}
        band = band_tiles(seq, cfg.window, (tiles["attn_band_fwd"],
                                            tiles["attn_band_bwd"]))
    # the rows the row movement works over, of the rows the buffers are
    # bound by (every assignment landing here, a tile of slack an expert)
    assignments = batch * seq * cfg.experts_per_token
    bound = gm.bound_rows(assignments, cfg.n_held,
                          gm.choose_tile(assignments, cfg.n_held))
    series = {
        "expert_load_max_over_mean": (
            stats["group_max"] / stats["group_mean"]).max(axis=1).tolist(),
        "moe_bound_in_use_share": (
            100.0 * stats["rows_in_use"].mean(axis=1) / bound).tolist()}
    if band:
        series["band_pairs_visited_over_needed"] = [
            (band["fwd_pairs"] + band["bwd_pairs"])
            / (2 * band["visible_pairs"])]
    return harness.Outcome(
        end_to_end={"tokens_per_s": steps * batch * seq / elapsed},
        attempted=steps, failed=0, setup_s=setup_s, device=device,
        counters={
            "calls_in_trace": steps_in_trace,
            "step_flops": float(np.mean([work_mellum.mellum_train_step(
                m, batch, seq, rows)["flops"] for rows in in_trace])),
            "sizes": m, "batch": batch, "sequence": seq,
            "routed_rows": in_trace.tolist(),
            "rows_in_use": stats["rows_in_use"].mean(0).tolist(),
            "bound_rows": bound,
            "group_max": stats["group_max"].max(0).tolist(),
            "group_mean": stats["group_mean"].mean(0).tolist(),
            "dropped": dropped,
            "band_calls": sorted(calls), "band_pairs": band,
            "series": series, "op_scopes": op_scopes},
        trace=window.reduce(1, op_scopes),
        counts={"steps": steps, "tokens_per_step": batch * seq,
                "routed_per_step": float(routed.sum(axis=1).mean())})
