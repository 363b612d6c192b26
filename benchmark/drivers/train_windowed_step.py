"""Driver of the window-and-full-attention training cells: the normal train
step of ``brpc_tpu/models/windowed.py`` (sliding-window layers and full
layers of different head counts in one stack, a rope a kind, a per-head
output gate, sigmoid-routed experts of which this chip holds a share beside
a shared expert; bf16 compute, float32 master weights, AdamW, donated state)
at a published configuration's widths.

The shape of ``train_hybrid_step.py``: set-up builds ONE object, the
compiled step with its state, makes the weights on the device from the seed
in one jitted call (``reference_swa.windowed_init``), drives the object
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
import reference_swa
import trace_reduce
import work_swa
from drivers.train_looped_step import leaf_gap
from drivers.train_moe_step import _agreement

N_BATCHES = 64           # distinct token batches, cycled through the window

_SIZES = ("hidden_size", "vocab_size", "num_hidden_layers",
          "intermediate_size", "num_key_value_heads", "head_dim",
          "sliding_window", "rope_parameters", "layer_types",
          "mlp_layer_types", "num_attention_heads_per_layer", "num_experts",
          "router_experts", "expert_offset", "num_experts_per_tok",
          "moe_intermediate_size", "shared_expert_intermediate_size",
          "moe_routed_scaling_factor", "rms_norm_eps")


def _leaf_sizes(m: dict) -> dict:
    """Numbers in every leaf of the weights' tree, by the leaf's name."""
    import jax

    tree = jax.eval_shape(lambda k: reference_swa.windowed_init(k, m),
                          jax.random.PRNGKey(0))
    return {jax.tree_util.keystr(path): leaf.size for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def _compare(ctx, got: dict, want: dict, m: dict) -> None:
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        name, gap = f"loss_gap_step{i + 1}", abs(a - b) / abs(b)
        if name in ctx.size("limits"):
            ctx.check(name, gap)
        else:
            print(f"benchmark: not compared {name}: {gap!r}", file=sys.stderr)
    # Every leaf against its own reference norm (W_g is hidden x heads beside
    # matrices of millions), and the matrices alone under a limit that a
    # fault of a few percent of one layer cannot pass (PERF.md section 2).
    ctx.check("grad_norm_gap", leaf_gap(
        got["grad_norms"], want["grad_norms"], "gradient gap"))
    matrices = [k for k, n in _leaf_sizes(m).items()
                if n >= m["hidden_size"] ** 2 // 4]
    ctx.check("matrix_grad_norm_gap", leaf_gap(
        {k: got["grad_norms"][k] for k in matrices},
        {k: want["grad_norms"][k] for k in matrices},
        "matrix gradient gap"))
    ctx.check("delta_norm_gap", leaf_gap(
        got["delta_norms"], want["delta_norms"], "change gap"))
    ctx.check("routing_disagreement",
              1.0 - _agreement(got["selected"], want["selected"]))


def model_config(windowed, m: dict):
    """The program's configuration from the published names."""
    full, window = (m["rope_parameters"][k]
                    for k in ("full_attention", "sliding_attention"))
    heads = dict(zip(m["layer_types"], m["num_attention_heads_per_layer"]))
    return windowed.WindowedConfig(
        vocab_size=m["vocab_size"], hidden=m["hidden_size"],
        n_layers=m["num_hidden_layers"],
        full_heads=heads["full_attention"],
        window_heads=heads["sliding_attention"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        window=m["sliding_window"], window_rope_theta=window["rope_theta"],
        full_rope_theta=full["rope_theta"],
        full_rotary_factor=full["partial_rotary_factor"],
        yarn_factor=full["factor"],
        yarn_original_positions=full["original_max_position_embeddings"],
        yarn_beta_fast=full["beta_fast"], yarn_beta_slow=full["beta_slow"],
        yarn_attention_factor=full["attention_factor"],
        intermediate=m["intermediate_size"], n_experts=m["router_experts"],
        experts_per_token=m["num_experts_per_tok"],
        moe_intermediate=m["moe_intermediate_size"],
        shared_intermediate=m["shared_expert_intermediate_size"],
        routed_scaling=m["moe_routed_scaling_factor"],
        norm_eps=m["rms_norm_eps"], n_held=m["num_experts"],
        expert_offset=m["expert_offset"])


def run(ctx: harness.Context) -> harness.Outcome:
    # First of all, before any device is touched: a checkout without the
    # model fails here, at once.
    sys.path.insert(0, harness.ROOT)
    from brpc_tpu.models import windowed
    from brpc_tpu.ops.flash_attention import band_calls, band_tiles

    import jax
    import optax

    devices = harness.jax_devices(ctx.cell["chips"], ctx.dry)
    ctx.lap("jax_devices")

    m = {k: ctx.size(k) for k in _SIZES}
    # under the key readers/train.kernel_roofline's routed_experts reads
    m["n_routed_experts"] = m["num_experts"]
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
            **{fault: {"fault": fault} for fault in reference_swa.FAULTS}}
        want = reference_swa.train_reference(ctx.seed, m, o, tokens,
                                             steps_followed)
        got = reference_swa.train_reference(ctx.seed, m, o, tokens,
                                            steps_followed,
                                            **variants[ctx.control])
        _compare(ctx, got, want, m)
        ctx.check("dropped_assignments", 0.0)
        return harness.Outcome(
            end_to_end={}, attempted=steps_followed, failed=0,
            setup_s=time.monotonic() - ctx.t_process,
            device=harness.device_report(devices, 1))

    cfg = model_config(windowed, m)
    optimizer = optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                            eps=o["eps"], weight_decay=o["weight_decay"])
    key = reference.seed_key(ctx.seed)
    params = jax.jit(lambda k: reference_swa.windowed_init(k, m))(key)
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
        lambda a, b: a - b, p, reference_swa.windowed_init(k, m))))

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
             for k in stats_log[0]}                     # each [steps, L - 1]
    del state, step, stats_log

    want = reference_swa.train_reference(ctx.seed, m, o, tokens,
                                         steps_followed)
    _compare(ctx, got, want, m)
    ctx.lap("reference")
    ctx.check("dropped_assignments", float(stats["dropped"].sum()))
    ctx.check("last_loss_not_finite", 0.0 if np.isfinite(last_loss) else 1.0,
              0.0)
    routed = stats["routed"][n_before:]
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
    series = {"expert_load_max_over_mean": (
        stats["group_max"][n_before:]
        / stats["group_mean"][n_before:]).max(axis=1).tolist()}
    if band:
        series["band_pairs_visited_over_needed"] = [
            (band["fwd_pairs"] + band["bwd_pairs"])
            / (2 * band["visible_pairs"])]
    return harness.Outcome(
        end_to_end={"tokens_per_s": steps * batch * seq / elapsed},
        attempted=steps, failed=0, setup_s=setup_s, device=device,
        counters={
            "calls_in_trace": steps_in_trace,
            "step_flops": float(np.mean([work_swa.windowed_train_step(
                m, batch, seq, rows)["flops"] for rows in in_trace])),
            "sizes": m, "batch": batch, "sequence": seq,
            "routed_rows": in_trace.tolist(),
            "rows_in_use": stats["rows_in_use"][n_before:].mean(0).tolist(),
            "group_max": stats["group_max"][n_before:].max(0).tolist(),
            "group_mean": stats["group_mean"][n_before:].mean(0).tolist(),
            "dropped": int(stats["dropped"].sum()),
            "band_calls": sorted(calls), "band_pairs": band,
            "series": series, "op_scopes": op_scopes},
        trace=window.reduce(1, op_scopes),
        counts={"steps": steps, "tokens_per_step": batch * seq,
                "routed_per_step": float(routed.sum(axis=1).mean())})
