"""Plain reference of the looped language model's train step (Ouro-2.6B):
the published equations — one stack of sandwich-normed layers run
``total_ut_steps`` times on the same weights, the final norm closing every
pass, an exit gate on each pass's normed state, the expected cross-entropy
under the exit distribution less ``exit_beta`` times its entropy — and AdamW,
in ``jax.numpy`` float32 at ``highest`` matmul precision. A Python loop over
passes and layers: no scan over the shared weights, no kernel.

Nothing here imports the program (``brpc_tpu.models``) or takes anything the
program made; ``reference.py``'s helpers (keys, tokens, AdamW, norms, the fp8
operand) are shared. ``m`` is the model's sizes under the names of the
published ``config.json``, plus ``exit_beta``.

What memory forces, and nothing else: every layer application and every
head is recomputed in the backward pass (``jax.checkpoint``), attention
takes one head at a time, and the loss takes blocks of 512 positions with
all passes of a block together, so that neither the [T, T] scores of every
head nor four [B, T, vocab] logits are live at once. The mathematics is that
of all heads and all positions.

``fault`` plants what the comparison has to catch: ``three_passes`` (one
pass fewer), ``norm_last_only`` (the final norm after the last pass alone),
``gate_detached`` (no gradient through the exit distribution),
``no_entropy`` (beta = 0).
"""

from __future__ import annotations

import reference

LOSS_BLOCK = 512


def looped_init(key, m: dict):
    """The weights of a run, from the seed's key, float32, one traced
    function: normal(0, fan_in^-0.5) matrices, unit gains, the gate's weight
    normal(0, hidden^-0.5) — so that lambda is not a constant 1/2 — and its
    bias 0. The tree is the one the program's step takes: per-layer tensors
    stacked on a leading axis."""
    import jax
    import jax.numpy as jnp

    h, n, v = m["hidden_size"], m["num_hidden_layers"], m["vocab_size"]
    inter = m["intermediate_size"]
    hq = m["num_attention_heads"] * m["head_dim"]
    hkv = m["num_key_value_heads"] * m["head_dim"]
    keys = iter(jax.random.split(key, 10))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) \
            * fan_in ** -0.5

    gain = lambda: jnp.ones((n, h), jnp.float32)  # noqa: E731
    return {
        "embed": dense((v, h), 1.0),
        "layers": {
            "wq": dense((n, h, hq), h), "wk": dense((n, h, hkv), h),
            "wv": dense((n, h, hkv), h), "wo": dense((n, hq, h), hq),
            "w_gate": dense((n, h, inter), h),
            "w_up": dense((n, h, inter), h),
            "w_down": dense((n, inter, h), inter),
            "attn_norm": gain(), "attn_out_norm": gain(),
            "mlp_norm": gain(), "mlp_out_norm": gain(),
        },
        "final_norm": jnp.ones((h,), jnp.float32),
        "lm_head": dense((h, v), h),
        "exit_gate": {"w": dense((h,), h), "b": jnp.zeros((), jnp.float32)},
    }


def exit_distribution(lam):
    """lambda of a position's passes [..., R] -> p [..., R]: p_r = lambda_r
    prod_{j<r}(1 - lambda_j), the last pass taking what is left."""
    import jax.numpy as jnp

    stay = jnp.cumprod(1.0 - lam, axis=-1)
    before = jnp.concatenate([jnp.ones_like(stay[..., :1]), stay[..., :-1]],
                             axis=-1)
    return jnp.concatenate([lam[..., :-1] * before[..., :-1],
                            before[..., -1:]], axis=-1)


def looped_loss(params, tokens, m: dict, *, matmul_in=None, fault=None):
    """(the loss, {"pass_loss" [R], "exit_mass" [R], "exit_entropy"}), each
    a mean over the positions that predict a token. ``matmul_in`` rounds
    every matmul operand (the low-precision control)."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.special import entr

    q8 = matmul_in or (lambda x: x)

    def mm(a, b):
        return jnp.matmul(q8(a), q8(b), precision="highest")

    def norm(x, w):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + m["rms_norm_eps"]) * w

    b, t = tokens.shape
    nh, nkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    passes = m["total_ut_steps"] - (fault == "three_passes")
    beta = 0.0 if fault == "no_entropy" else m["exit_beta"]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def rope(x):                                     # [B,T,H,D], halves
        freqs = m["rope_theta"] ** (
            -jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    @jax.checkpoint
    def attend(qkv):
        """One head: [b,t,d] each."""
        q, k, v = qkv
        s = jnp.einsum("btd,bsd->bts", q8(q), q8(k),
                       precision="highest") * d ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", q8(p), q8(v), precision="highest")

    @jax.checkpoint
    def layer(x, lp):
        y = norm(x, lp["attn_norm"])
        q = rope(mm(y, lp["wq"]).reshape(b, t, nh, d))
        k = rope(mm(y, lp["wk"]).reshape(b, t, nkv, d))
        v = mm(y, lp["wv"]).reshape(b, t, nkv, d)
        k, v = (jnp.repeat(a, nh // nkv, axis=2) for a in (k, v))
        o = jax.lax.map(attend, tuple(jnp.moveaxis(a, 2, 0)
                                      for a in (q, k, v)))
        o = mm(jnp.moveaxis(o, 0, 2).reshape(b, t, nh * d), lp["wo"])
        x = x + norm(o, lp["attn_out_norm"])
        y = norm(x, lp["mlp_norm"])
        o = mm(jax.nn.silu(mm(y, lp["w_gate"])) * mm(y, lp["w_up"]),
               lp["w_down"])
        return x + norm(o, lp["mlp_out_norm"])

    x = params["embed"][tokens]
    states = []
    for r in range(passes):
        for i in range(m["num_hidden_layers"]):
            x = layer(x, {k: v[i] for k, v in params["layers"].items()})
        if fault != "norm_last_only" or r == passes - 1:
            x = norm(x, params["final_norm"])
        states.append(x)

    @jax.checkpoint
    def block(args):
        """The positions of one block, all passes: [R, n, H], [n] -> the
        per-position loss, cross-entropies [n, R], p [n, R], H(p)."""
        xs, targets = args
        logits = mm(xs, params["lm_head"])                   # [R, n, vocab]
        nll = (jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, targets[None, :, None], axis=-1)[..., 0]).T
        lam = jax.nn.sigmoid(mm(xs, params["exit_gate"]["w"])
                             + params["exit_gate"]["b"]).T
        p = exit_distribution(lam)
        entropy = jnp.sum(entr(p), axis=-1)
        weight, held = jax.lax.stop_gradient((p, entropy)) \
            if fault == "gate_detached" else (p, entropy)
        return jnp.sum(weight * nll, axis=-1) - beta * held, nll, p, entropy

    # Positions that predict a token, flattened and padded to whole blocks;
    # the padding is weighted nought.
    n = b * (t - 1)
    pad = -n % LOSS_BLOCK
    xs = jnp.stack([s[:, :-1].reshape(n, -1) for s in states])
    xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
    targets = jnp.pad(tokens[:, 1:].reshape(n), (0, pad))
    counted = (jnp.arange(n + pad) < n).astype(jnp.float32)
    out = jax.lax.map(block, (
        jnp.moveaxis(xs.reshape(passes, -1, LOSS_BLOCK, xs.shape[-1]), 1, 0),
        targets.reshape(-1, LOSS_BLOCK)))
    loss, nll, p, entropy = (
        jnp.tensordot(counted, a.reshape(n + pad, *a.shape[2:]), axes=1,
                      precision="highest") / n
        for a in out)
    return loss, {"pass_loss": nll, "exit_mass": p, "exit_entropy": entropy}


def train_reference(seed: int, m: dict, o: dict, tokens, steps: int, *,
                    matmul_in=None, fault=None) -> dict:
    """Follows the first ``steps`` steps from the seed, as
    ``reference.train_reference`` does: each step's loss, the first step's
    per-pass loss, mean exit mass per pass and mean exit entropy, the
    per-leaf norm of the first gradient, and the per-leaf norm of the
    parameters' change after the last step. Gradients and updates are
    separate donated programs, and between updates the moments wait on the
    host, so that the gradient program has the device to itself."""
    import jax
    import jax.numpy as jnp

    key = reference.seed_key(seed)
    params = jax.jit(lambda k: looped_init(k, m))(key)
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
    mu = nu = None
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t: looped_loss(p, t, m, matmul_in=matmul_in, fault=fault),
        has_aux=True))
    update = jax.jit(
        lambda p, a, b, g, c: reference.adamw_update(p, a, b, g, c, o),
        donate_argnums=(0, 1, 2, 3))
    norms = jax.jit(reference.leaf_norms)
    delta = jax.jit(lambda p, k: reference.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, looped_init(k, m))))
    losses, first = [], {}
    for i in range(steps):
        (loss, stats), grads = grad_fn(params, tokens[i])
        losses.append(float(loss))
        if i == 0:
            first = {"grad_norms": {k: float(v)
                                    for k, v in norms(grads).items()},
                     **{k: jax.device_get(v).tolist()
                        for k, v in stats.items()}}
        moments = (zeros(params), zeros(params)) if mu is None else \
            jax.device_put((mu, nu))
        params, *moments = update(params, *moments, grads, i + 1)
        if i + 1 < steps:
            mu, nu = jax.device_get(moments)
        del moments, grads
    delta_norms = {k: float(v) for k, v in delta(params, key).items()}
    return {"losses": losses, "delta_norms": delta_norms, **first}
