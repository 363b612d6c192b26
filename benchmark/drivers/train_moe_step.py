"""Driver of the sparse-expert training cells: the normal train step of
``brpc_tpu/models/deepseek.py`` (latent attention, sigmoid-routed experts of
which this chip holds a share; bf16 compute, float32 master weights, AdamW,
donated state) at a published configuration's widths.

The shape of ``train_step.py``: set-up builds ONE object, the compiled step
with its state, makes the weights on the device from the seed in one jitted
call (``reference_dsv3.dsv3_init``), drives the object through its first
steps and hands the same object to the window; the comparison follows those
steps with the plain float32 reference once the window has closed and the
state is freed. Beside the loss the step returns ``stats`` (assignments
routed to held experts, the experts' load, assignments dropped, the
selections); nothing reads them inside the window.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import harness
import reference
import reference_dsv3
import trace_reduce
import work_dsv3

N_BATCHES = 64           # distinct token batches, cycled through the window

_SIZES = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "vocab_size", "num_hidden_layers", "first_k_dense_replace",
          "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
          "v_head_dim", "kv_lora_rank", "n_routed_experts", "router_experts",
          "expert_offset", "n_shared_experts", "num_experts_per_tok",
          "routed_scaling_factor", "rms_norm_eps", "rope_theta")


def _agreement(a, b) -> float:
    """The share of (token, expert) selections that two routings have in
    common. a, b: [L, N, k] expert ids."""
    a, b = np.asarray(a), np.asarray(b)
    return float((a[..., :, None] == b[..., None, :]).any(-1).mean())


def _compare(ctx, got: dict, want: dict) -> None:
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        name, gap = f"loss_gap_step{i + 1}", abs(a - b) / abs(b)
        if name in ctx.size("limits"):
            ctx.check(name, gap)
        else:
            print(f"benchmark: not compared {name}: {gap!r}", file=sys.stderr)
    ctx.check("grad_norm_gap", reference.worst_leaf_gap(
        got["grad_norms"], want["grad_norms"]))
    moving = reference.moving_leaves(want["grad_norms"])
    ctx.check("delta_norm_gap", reference.worst_leaf_gap(
        {k: got["delta_norms"][k] for k in moving},
        {k: want["delta_norms"][k] for k in moving}))
    ctx.check("routing_disagreement",
              1.0 - _agreement(got["selected"], want["selected"]))


def run(ctx: harness.Context) -> harness.Outcome:
    # First of all, before any device is touched: a checkout without the
    # model fails here, at once.
    sys.path.insert(0, harness.ROOT)
    from brpc_tpu.models import deepseek

    import jax
    import optax

    devices = harness.jax_devices(ctx.cell["chips"], ctx.dry)
    ctx.lap("jax_devices")

    m = {k: ctx.size(k) for k in _SIZES}
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
            "drop_sixth": {"fault": "drop_sixth"},
            "no_scaling": {"fault": "no_scaling"},
        }
        want = reference_dsv3.train_reference(ctx.seed, m, o, tokens,
                                              steps_followed)
        got = reference_dsv3.train_reference(ctx.seed, m, o, tokens,
                                             steps_followed,
                                             **variants[ctx.control])
        _compare(ctx, got, want)
        ctx.check("dropped_assignments", 0.0)
        return harness.Outcome(
            end_to_end={}, attempted=steps_followed, failed=0,
            setup_s=time.monotonic() - ctx.t_process,
            device=harness.device_report(devices, 1))

    cfg = deepseek.DeepseekConfig(
        vocab_size=m["vocab_size"], hidden=m["hidden_size"],
        n_layers=m["num_hidden_layers"],
        n_dense_layers=m["first_k_dense_replace"],
        n_heads=m["num_attention_heads"], qk_nope_dim=m["qk_nope_head_dim"],
        qk_rope_dim=m["qk_rope_head_dim"], v_dim=m["v_head_dim"],
        kv_lora_rank=m["kv_lora_rank"], intermediate=m["intermediate_size"],
        moe_intermediate=m["moe_intermediate_size"],
        n_routed_experts=m["router_experts"],
        n_shared_experts=m["n_shared_experts"],
        experts_per_token=m["num_experts_per_tok"],
        routed_scaling=m["routed_scaling_factor"],
        rope_theta=m["rope_theta"], norm_eps=m["rms_norm_eps"],
        n_held=m["n_routed_experts"], expert_offset=m["expert_offset"])
    optimizer = optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                            eps=o["eps"], weight_decay=o["weight_decay"])
    key = reference.seed_key(ctx.seed)
    params = jax.jit(lambda k: reference_dsv3.dsv3_init(k, m))(key)
    opt_state = jax.jit(optimizer.init)(params)
    jax.block_until_ready(opt_state)
    ctx.lap("weights_from_seed")
    step = jax.jit(deepseek.make_train_step(cfg, optimizer),
                   donate_argnums=(0, 1)).lower(
                       params, opt_state, tokens[0]).compile()
    ctx.lap("compile_step")
    first_moment = jax.jit(lambda s: reference.leaf_norms(s[0].mu))
    change = jax.jit(lambda p, k: reference.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, reference_dsv3.dsv3_init(k, m))))

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
    del state, step, stats_log

    want = reference_dsv3.train_reference(ctx.seed, m, o, tokens,
                                          steps_followed)
    _compare(ctx, got, want)
    ctx.lap("reference")
    ctx.check("dropped_assignments", float(stats["dropped"].sum()))
    ctx.check("last_loss_not_finite", 0.0 if np.isfinite(last_loss) else 1.0,
              0.0)
    routed = stats["routed"][n_before:]
    in_trace = routed[:steps_in_trace] if steps_in_trace else routed
    return harness.Outcome(
        end_to_end={"tokens_per_s": steps * batch * seq / elapsed},
        attempted=steps, failed=0, setup_s=setup_s, device=device,
        counters={
            "calls_in_trace": steps_in_trace,
            "step_flops": float(np.mean([work_dsv3.dsv3_train_step(
                m, batch, seq, rows)["flops"] for rows in in_trace])),
            "sizes": m, "batch": batch, "sequence": seq,
            "routed_rows": in_trace.tolist(),
            "series": {"expert_load_max_over_mean": (
                stats["group_max"][n_before:]
                / stats["group_mean"][n_before:]).max(axis=1).tolist()},
            "op_scopes": op_scopes},
        trace=window.reduce(1, op_scopes),
        counts={"steps": steps, "tokens_per_step": batch * seq,
                "routed_per_step": float(routed.sum(axis=1).mean())})
