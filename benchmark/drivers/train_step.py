"""Driver of the training cells: the normal train step of
``brpc_tpu/models/llama.py`` (bf16 compute, float32 master weights, AdamW,
donated state) at a published configuration's widths.

Set-up builds ONE object, the compiled step with its state, makes the
weights on the device from the seed in one jitted call, drives the object
through its first steps (each on other tokens), and hands the same object
to the window. The comparison follows those first steps with the plain
float32 reference once the window has closed and the state is freed.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import harness
import reference
import trace_reduce
import work

N_BATCHES = 64           # distinct token batches, cycled through the window


def _model_sizes(ctx) -> dict:
    keys = ("hidden_size", "intermediate_size", "vocab_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta")
    return {k: ctx.size(k) for k in keys}


def _compare(ctx, got: dict, want: dict) -> None:
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        name, gap = f"loss_gap_step{i + 1}", abs(a - b) / abs(b)
        if name in ctx.size("limits"):
            ctx.check(name, gap)
        else:       # no control or fault reads above sound runs (PERF.md)
            print(f"benchmark: not compared {name}: {gap!r}", file=sys.stderr)
    ctx.check("grad_norm_gap", reference.worst_leaf_gap(
        got["grad_norms"], want["grad_norms"]))
    moving = reference.moving_leaves(want["grad_norms"])
    ctx.check("delta_norm_gap", reference.worst_leaf_gap(
        {k: got["delta_norms"][k] for k in moving},
        {k: want["delta_norms"][k] for k in moving}))


def run(ctx: harness.Context) -> harness.Outcome:
    import jax
    import jax.numpy as jnp
    import optax

    devices = harness.jax_devices(ctx.cell["chips"], ctx.dry)
    ctx.lap("jax_devices")
    sys.path.insert(0, harness.ROOT)
    from brpc_tpu.models import llama

    m = _model_sizes(ctx)
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
        }
        want = reference.train_reference(ctx.seed, m, o, tokens,
                                          steps_followed)
        got = reference.train_reference(ctx.seed, m, o, tokens,
                                        steps_followed,
                                        **variants[ctx.control])
        _compare(ctx, got, want)
        return harness.Outcome(
            end_to_end={}, attempted=steps_followed, failed=0,
            setup_s=time.monotonic() - ctx.t_process,
            device=harness.device_report(devices, 1))

    cfg = llama.LlamaConfig(
        vocab_size=m["vocab_size"], hidden=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        intermediate=m["intermediate_size"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"])
    optimizer = optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                            eps=o["eps"], weight_decay=o["weight_decay"])
    key = reference.seed_key(ctx.seed)
    params = jax.jit(lambda k: reference.llama_init(k, m))(key)
    opt_state = jax.jit(optimizer.init)(params)
    jax.block_until_ready(opt_state)
    ctx.lap("weights_from_seed")
    step = jax.jit(llama.make_train_step(cfg, optimizer),
                   donate_argnums=(0, 1)).lower(
                       params, opt_state, tokens[0]).compile()
    ctx.lap("compile_step")
    first_moment = jax.jit(lambda s: reference.leaf_norms(s[0].mu))
    change = jax.jit(lambda p, k: reference.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, reference.llama_init(k, m))))

    state = [params, opt_state, 0]      # the one object: set-up's and the window's
    del params, opt_state

    def one_step():
        state[0], state[1], loss = step(state[0], state[1],
                                        tokens[state[2] % N_BATCHES])
        state[2] += 1
        return loss

    # The object's first steps, through the window's own call and feed.
    got = {"losses": []}
    for i in range(steps_followed):
        got["losses"].append(float(one_step()))
        if i == 0:      # mu_1 = (1 - b1) * g_1: the gradient as Adam got it
            got["grad_norms"] = {k: float(v) / (1 - o["b1"]) for k, v in
                                 first_moment(state[1]).items()}
    got["delta_norms"] = {k: float(v) for k, v in change(state[0], key).items()}
    jax.block_until_ready(state[0])
    ctx.lap("first_steps")

    # -- the window: back to back, at most two steps in flight ------------
    window = harness.TracedWindow(ctx.trace and not ctx.dry)
    window.start()
    setup_s = time.monotonic() - ctx.t_process
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
    del state, step

    want = reference.train_reference(ctx.seed, m, o, tokens, steps_followed)
    _compare(ctx, got, want)
    ctx.lap("reference")
    ctx.check("last_loss_not_finite", 0.0 if np.isfinite(last_loss) else 1.0,
              0.0)
    return harness.Outcome(
        end_to_end={"tokens_per_s": steps * batch * seq / elapsed},
        attempted=steps, failed=0, setup_s=setup_s, device=device,
        counters={"calls_in_trace": steps_in_trace,
                  "step_flops": work.llama_train_step(m, batch, seq)["flops"],
                  "series": {}, "op_scopes": op_scopes},
        trace=window.reduce(1, op_scopes),
        counts={"steps": steps, "tokens_per_step": batch * seq})
