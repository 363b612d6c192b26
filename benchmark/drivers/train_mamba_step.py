"""Driver of the hybrid Mamba-2 training cell: the normal train step of
``brpc_tpu/models/mamba.py`` (nine Mamba-2 layers to one attention layer
without positions, laid out from the published ``layer_types``, a dense MLP
after every mixer, a tied embedding and head over this chip's slice of the
vocabulary; bf16 compute, float32 master weights, AdamW, donated state) at
the published configuration's widths.

The shape of ``train_hybrid_step.py``: set-up builds ONE object, the
compiled step with its state, makes the weights on the device from the seed
in one jitted call (``reference_mamba.init``), drives the object
through its first steps and hands the same object to the window; the
comparison follows those steps with the plain float32 reference (the
per-token recurrence) once the window has closed and the state is freed.
The model has no experts: the step's stats are empty and nothing is routed.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import harness
import reference
import reference_mamba
import trace_reduce
import work_mamba

N_BATCHES = 64           # distinct token batches, cycled through the window

_SIZES = ("hidden_size", "vocab_size", "num_hidden_layers", "layer_types",
          "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
          "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
          "mamba_conv_bias", "mamba_proj_bias", "shared_intermediate_size",
          "num_local_experts", "position_embedding_type",
          "tie_word_embeddings", "rms_norm_eps", "embedding_multiplier",
          "residual_multiplier", "attention_multiplier", "logits_scaling")


def model_config(mamba, m: dict):
    """The program's configuration from the published names; what the file
    states that the program does not build is refused here, before any
    device is touched."""
    built = {"mamba_n_groups": 1, "mamba_conv_bias": True,
             "mamba_proj_bias": False, "num_local_experts": 0,
             "position_embedding_type": "nope", "tie_word_embeddings": True}
    for key, value in built.items():
        if m[key] != value:
            raise SystemExit(f"benchmark: {key} {m[key]!r} is not built "
                             f"(only {value!r})")
    return mamba.MambaConfig(
        vocab_size=m["vocab_size"], hidden=m["hidden_size"],
        n_layers=m["num_hidden_layers"],
        layer_types=tuple(m["layer_types"]),
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], ssm_heads=m["mamba_n_heads"],
        ssm_head_dim=m["mamba_d_head"], ssm_state=m["mamba_d_state"],
        conv_kernel=m["mamba_d_conv"],
        intermediate=m["shared_intermediate_size"],
        norm_eps=m["rms_norm_eps"],
        embedding_multiplier=m["embedding_multiplier"],
        residual_multiplier=m["residual_multiplier"],
        attention_multiplier=m["attention_multiplier"],
        logits_scaling=m["logits_scaling"])


def leaf_gap(got: dict, want: dict, what: str) -> float:
    """The widest gap between a leaf's norm here and in the reference,
    against the reference's norm of THAT leaf, or a thousandth of the
    median leaf's where the leaf's own is nought to rounding. Says which
    leaf."""
    floor = 1e-3 * float(np.median(list(want.values())))
    gaps = {k: abs(got[k] - want[k]) / max(want[k], floor) for k in want}
    worst = max(gaps, key=gaps.get)
    print(f"benchmark: widest {what} at {worst}: {got[worst]!r} "
          f"(reference {want[worst]!r})", file=sys.stderr)
    return gaps[worst]


def _matrices(m: dict) -> list:
    """The leaves of the weights' tree that hold a projection's worth of
    numbers and more (the attention's w_k, the smallest), by the leaf's
    name."""
    import jax

    tree = jax.eval_shape(lambda k: reference_mamba.mamba_init(k, m),
                          jax.random.PRNGKey(0))
    least = m["hidden_size"] ** 2 * m["num_key_value_heads"] \
        // m["num_attention_heads"]
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
    # Every leaf against its own reference norm (a_log, dt_bias and d are
    # 576 numbers beside matrices of millions), and the matrices alone under
    # a limit that a fault of a few percent of one layer cannot pass.
    ctx.check("grad_norm_gap", leaf_gap(
        got["grad_norms"], want["grad_norms"], "gradient gap"))
    matrices = _matrices(m)
    ctx.check("matrix_grad_norm_gap", leaf_gap(
        {k: got["grad_norms"][k] for k in matrices},
        {k: want["grad_norms"][k] for k in matrices},
        "matrix gradient gap"))
    ctx.check("delta_norm_gap", leaf_gap(
        got["delta_norms"], want["delta_norms"], "change gap"))


def run(ctx: harness.Context) -> harness.Outcome:
    # First of all, before any device is touched: a checkout without the
    # model fails here, at once.
    sys.path.insert(0, harness.ROOT)
    from brpc_tpu.models import mamba

    m = {k: ctx.size(k) for k in _SIZES}
    cfg = model_config(mamba, m)

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
            **{fault: {"fault": fault} for fault in reference_mamba.FAULTS}}
        want = reference_mamba.train_reference(ctx.seed, m, o, tokens,
                                               steps_followed)
        got = reference_mamba.train_reference(ctx.seed, m, o, tokens,
                                              steps_followed,
                                              **variants[ctx.control])
        _compare(ctx, got, want, m)
        return harness.Outcome(
            end_to_end={}, attempted=steps_followed, failed=0,
            setup_s=time.monotonic() - ctx.t_process,
            device=harness.device_report(devices, 1))

    optimizer = optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                            eps=o["eps"], weight_decay=o["weight_decay"])
    key = reference.seed_key(ctx.seed)
    params = reference_mamba.init(key, m)
    # the weights the parameters' change is taken from, on the host: the
    # state and a second draw of them would not fit the chip together
    initial = jax.device_get(params)
    opt_state = jax.jit(optimizer.init)(params)
    jax.block_until_ready(opt_state)
    ctx.lap("weights_from_seed")
    step = jax.jit(mamba.make_train_step(cfg, optimizer),
                   donate_argnums=(0, 1)).lower(
                       params, opt_state, tokens[0]).compile()
    ctx.lap("compile_step")
    first_moment = jax.jit(lambda s: reference.leaf_norms(s[0].mu))

    state = [params, opt_state, 0]      # the one object: set-up's and the window's
    del params, opt_state
    got = {"losses": []}

    def one_step():
        state[0], state[1], loss, _ = step(
            state[0], state[1], tokens[state[2] % N_BATCHES])
        state[2] += 1
        return loss

    # The object's first steps, through the window's own call and feed.
    for i in range(steps_followed):
        got["losses"].append(float(one_step()))
        if i == 0:      # mu_1 = (1 - b1) * g_1: the gradient as Adam got it
            got["grad_norms"] = {k: float(v) / (1 - o["b1"]) for k, v in
                                 first_moment(state[1]).items()}
    got["delta_norms"] = {k: float(v) for k, v in reference_mamba.change_norms(
        state[0], initial).items()}
    del initial
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

    want = reference_mamba.train_reference(ctx.seed, m, o, tokens,
                                           steps_followed)
    _compare(ctx, got, want, m)
    ctx.lap("reference")
    ctx.check("last_loss_not_finite", 0.0 if np.isfinite(last_loss) else 1.0,
              0.0)
    return harness.Outcome(
        end_to_end={"tokens_per_s": steps * batch * seq / elapsed},
        attempted=steps, failed=0, setup_s=setup_s, device=device,
        counters={
            "calls_in_trace": steps_in_trace,
            "step_flops": work_mamba.mamba_train_step(m, batch, seq)["flops"],
            "sizes": m, "batch": batch, "sequence": seq,
            "op_scopes": op_scopes},
        trace=window.reduce(1, op_scopes),
        counts={"steps": steps, "tokens_per_step": batch * seq})
