"""Driver of the looped-language-model training cells: the normal train step
of ``brpc_tpu/models/looped.py`` (one stack of layers run ``total_ut_steps``
times on shared weights, an exit gate and a head after every pass, the loss
over all passes; bf16 compute, float32 master weights, AdamW, donated state)
at a published configuration's widths.

The shape of ``train_moe_step.py``: set-up builds ONE object, the compiled
step with its state, makes the weights on the device from the seed in one
jitted call (``reference_looped.looped_init``), drives the object through its
first steps and hands the same object to the window; the comparison follows
those steps with the plain float32 reference once the window has closed and
the state is freed. Beside the loss the step returns ``stats`` (each pass's
loss, the mean exit mass of each pass, the exit distribution's mean
entropy); nothing reads them inside the window.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import harness
import reference
import reference_looped
import trace_reduce
import work_looped

N_BATCHES = 64           # distinct token batches, cycled through the window

_SIZES = ("hidden_size", "intermediate_size", "vocab_size",
          "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
          "head_dim", "total_ut_steps", "rms_norm_eps", "rope_theta",
          "exit_beta")

_FAULTS = ("three_passes", "norm_last_only", "gate_detached", "no_entropy")


def _gate_as_one(norms: dict) -> dict:
    """The gate's weight and bias as one leaf, the Linear(hidden, 1) they
    are: the bias is one number whose gradient is a mean of terms of either
    sign, and its own norm carries no digits to compare."""
    gate = [k for k in norms if "exit_gate" in k]
    rest = {k: v for k, v in norms.items() if k not in gate}
    return {**rest, "['exit_gate']": float(np.sqrt(sum(
        norms[k] ** 2 for k in gate)))}


def leaf_gap(got: dict, want: dict, what: str) -> float:
    """The widest gap between a leaf's norm here and in the reference,
    against the reference's norm of THAT leaf (the gate is 2,049 numbers
    beside matrices of millions: against the median leaf it could vanish
    whole and not be seen), or a thousandth of the median leaf's where the
    leaf's own is nought to rounding. Says which leaf."""
    got, want = _gate_as_one(got), _gate_as_one(want)
    floor = 1e-3 * float(np.median(list(want.values())))
    gaps = {k: abs(got[k] - want[k]) / max(want[k], floor) for k in want}
    worst = max(gaps, key=gaps.get)
    print(f"benchmark: widest {what} at {worst}: {got[worst]!r} "
          f"(reference {want[worst]!r})", file=sys.stderr)
    return gaps[worst]


def _per_pass_gap(got, want, relative: bool) -> float:
    """The widest gap over the passes; a pass too few or too many reads 1."""
    got, want = np.asarray(got, float), np.asarray(want, float)
    if got.shape != want.shape:
        return 1.0
    gap = np.abs(got - want)
    return float(np.max(gap / np.abs(want) if relative else gap))


def _compare(ctx, got: dict, want: dict) -> None:
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        name, gap = f"loss_gap_step{i + 1}", abs(a - b) / abs(b)
        if name in ctx.size("limits"):
            ctx.check(name, gap)
        else:
            print(f"benchmark: not compared {name}: {gap!r}", file=sys.stderr)
    ctx.check("pass_loss_gap", _per_pass_gap(
        got["pass_loss"], want["pass_loss"], relative=True))
    for name in ("grad", "delta"):
        ctx.check(f"{name}_norm_gap", leaf_gap(
            got[f"{name}_norms"], want[f"{name}_norms"], f"{name}_norm_gap"))
    ctx.check("exit_mass_gap", _per_pass_gap(
        got["exit_mass"], want["exit_mass"], relative=False))
    print(f"benchmark: not compared exit_entropy: {got['exit_entropy']!r} "
          f"(reference {want['exit_entropy']!r})", file=sys.stderr)


def run(ctx: harness.Context) -> harness.Outcome:
    # First of all, before any device is touched: a checkout without the
    # model fails here, at once.
    sys.path.insert(0, harness.ROOT)
    from brpc_tpu.models import looped

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
        variant = {"lowprec": {"matmul_in": reference.fp8_operand},
                   **{f: {"fault": f} for f in _FAULTS}}[ctx.control]
        want = reference_looped.train_reference(ctx.seed, m, o, tokens,
                                                steps_followed)
        got = reference_looped.train_reference(ctx.seed, m, o, tokens,
                                               steps_followed, **variant)
        _compare(ctx, got, want)
        return harness.Outcome(
            end_to_end={}, attempted=steps_followed, failed=0,
            setup_s=time.monotonic() - ctx.t_process,
            device=harness.device_report(devices, 1))

    cfg = looped.LoopedConfig(
        vocab_size=m["vocab_size"], hidden=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        intermediate=m["intermediate_size"],
        total_ut_steps=m["total_ut_steps"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"], exit_beta=m["exit_beta"])
    optimizer = optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                            eps=o["eps"], weight_decay=o["weight_decay"])
    key = reference.seed_key(ctx.seed)
    params = jax.jit(lambda k: reference_looped.looped_init(k, m))(key)
    opt_state = jax.jit(optimizer.init)(params)
    jax.block_until_ready(opt_state)
    ctx.lap("weights_from_seed")
    step = jax.jit(looped.make_train_step(cfg, optimizer),
                   donate_argnums=(0, 1)).lower(
                       params, opt_state, tokens[0]).compile()
    ctx.lap("compile_step")
    first_moment = jax.jit(lambda s: reference.leaf_norms(s[0].mu))
    change = jax.jit(lambda p, k: reference.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, reference_looped.looped_init(k, m))))

    state = [params, opt_state, 0]      # the one object: set-up's and the window's
    del params, opt_state
    stats_log = []                      # device arrays; read after the window

    def one_step():
        state[0], state[1], loss, stats = step(
            state[0], state[1], tokens[state[2] % N_BATCHES])
        stats_log.append(stats)
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
    got.update({k: np.asarray(v).tolist() for k, v in stats_log[0].items()})
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
    entropy = [float(s["exit_entropy"]) for s in stats_log[n_before:]]
    del state, step, stats_log

    want = reference_looped.train_reference(ctx.seed, m, o, tokens,
                                            steps_followed)
    _compare(ctx, got, want)
    ctx.lap("reference")
    ctx.check("last_loss_not_finite", 0.0 if np.isfinite(last_loss) else 1.0,
              0.0)
    return harness.Outcome(
        end_to_end={"tokens_per_s": steps * batch * seq / elapsed},
        attempted=steps, failed=0, setup_s=setup_s, device=device,
        counters={
            "calls_in_trace": steps_in_trace,
            "step_flops": work_looped.looped_train_step(
                m, batch, seq)["flops"],
            "sizes": m, "batch": batch, "sequence": seq,
            "series": {"exit_entropy": entropy}, "op_scopes": op_scopes},
        trace=window.reduce(1, op_scopes),
        counts={"steps": steps, "tokens_per_step": batch * seq,
                "exit_entropy": float(np.median(entropy))})
