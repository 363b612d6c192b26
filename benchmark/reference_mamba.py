"""Plain reference of the hybrid Mamba-2 / attention train step
(granite-4.0-h-micro: nine Mamba-2 layers to one attention layer without
positions, a dense SwiGLU MLP after every mixer, a tied embedding and head)
and AdamW, in ``jax.numpy`` float32 at ``highest`` matmul precision. No
kernel, no chunked form: the state-space scan is the published per-token
recurrence, per head from S = 0,

    S_t = exp(Delta_t A) S_{t-1} + Delta_t B_t x_t^T       y_t = S_t^T C_t + D x_t

a ``lax.scan`` over positions (wrapped in ``jax.checkpoint`` a block of
``SCAN_BLOCK`` positions at a time, so that the backward pass recomputes a
block's states and never holds a per-token history: 2 MB a position and
layer at the published widths); the convolution is its shifted adds;
attention is dense one head at a time.

Nothing here imports the program (``brpc_tpu``) or takes anything the program
made; ``reference.py``'s helpers (keys, AdamW, norms, the fp8 operand) are
shared. ``m`` is the model's sizes under the names of the published
``config.json``.

Departures from the published model, the same as the program's: ``in_proj``
is two leaves, ``in_proj`` laid xBC | z and ``dt_proj`` (the released
matrix lays z | xBC | dt: a column permutation and split of random
weights); the time step is not clamped (``time_step_limit`` (0, inf), the
released default); no auxiliary loss.
"""

from __future__ import annotations

import functools

import reference

SCAN_BLOCK = 256        # positions of the recurrence kept at once
FAULTS = ("no_skip", "norm_then_gate", "no_conv_bias", "no_decay")


def layout(m: dict) -> tuple:
    """(periods, Mamba layers a period, the attention layer's place among
    them) of ``layer_types`` up to the depth held."""
    kinds = list(m["layer_types"][:m["num_hidden_layers"]])
    periods = kinds.count("attention")
    length = len(kinds) // periods
    period = kinds[:length]
    assert period * periods == kinds and period.count("attention") == 1
    return periods, length - 1, period.index("attention")


def _dims(m: dict):
    nh, p, n = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    assert m["mamba_n_groups"] == 1
    return nh, p, n, nh * p, nh * p + 2 * n


def mamba_init(key, m: dict):
    """The weights of a run, from the seed's key, float32, one traced
    function: normal(0, fan_in^-0.5) matrices (the tied embedding by the
    hidden size, as the head reads it), the convolution's taps normal(0,
    kernel^-0.5) and bias U(-kernel^-0.5, kernel^-0.5), norms 1, ``a_log``
    log U(1, 16), ``dt_bias`` softplus^-1 of a step log-uniform in
    [1e-3, 0.1], ``d`` 1. The tree is the one the program's step takes:
    Mamba layers stacked [periods, Mamba layers a period, ...], attention
    layers [periods, ...]. Every normal number is one draw, and every
    uniform one another, cut into the leaves in order: two random programs
    where a draw a leaf would make eighteen (the most of the program's
    compile time on a CPU)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    h, v = m["hidden_size"], m["vocab_size"]
    f, taps = m["shared_intermediate_size"], m["mamba_d_conv"]
    nh, _, _, d_in, conv = _dims(m)
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    d = h // heads
    periods, n_mamba, _ = layout(m)

    def tree(dense, uniform):
        def mlp(lead):
            return {"w_gate": dense(lead + (h, f), h),
                    "w_up": dense(lead + (h, f), h),
                    "w_down": dense(lead + (f, h), f),
                    "mixer_norm": jnp.ones(lead + (h,), jnp.float32),
                    "mlp_norm": jnp.ones(lead + (h,), jnp.float32)}

        lead = (periods, n_mamba)
        step = jnp.exp(uniform(lead + (nh,), np.log(1e-3), np.log(0.1)))
        return {
            "embed": dense((v, h), h),
            "mamba": {
                **mlp(lead),
                "in_proj": dense(lead + (h, conv + d_in), h),
                "dt_proj": dense(lead + (h, nh), h),
                "conv": dense(lead + (taps, conv), taps),
                "conv_bias": uniform(lead + (conv,), -taps ** -0.5,
                                     taps ** -0.5),
                "out_proj": dense(lead + (d_in, h), d_in),
                "a_log": jnp.log(uniform(lead + (nh,), 1.0, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "d": jnp.ones(lead + (nh,), jnp.float32),
                "out_norm": jnp.ones(lead + (d_in,), jnp.float32),
            },
            "attention": {
                **mlp((periods,)),
                "wq": dense((periods, h, heads * d), h),
                "wk": dense((periods, h, kv * d), h),
                "wv": dense((periods, h, kv * d), h),
                "wo": dense((periods, heads * d, h), heads * d),
            },
            "final_norm": jnp.ones((h,), jnp.float32),
        }

    # the leaves' shapes, in the order they are cut from the two draws
    wanted = {"normal": [], "uniform": []}
    tree(lambda shape, _: wanted["normal"].append(shape) or jnp.zeros(shape),
         lambda shape, *_: wanted["uniform"].append(shape)
         or jnp.zeros(shape))
    k_normal, k_uniform = jax.random.split(key)
    drawn = {kind: iter(np.cumsum([0] + [int(np.prod(x)) for x in shapes]))
             for kind, shapes in wanted.items()}
    flat = {kind: draw(k, (sum(int(np.prod(x)) for x in wanted[kind]),),
                       jnp.float32)
            for kind, draw, k in (("normal", jax.random.normal, k_normal),
                                  ("uniform", jax.random.uniform,
                                   k_uniform))}
    for ends in drawn.values():
        next(ends)

    def cut(kind, shape):
        stop = next(drawn[kind])
        return flat[kind][stop - int(np.prod(shape)):stop].reshape(shape)

    return tree(lambda shape, fan_in: cut("normal", shape) * fan_in ** -0.5,
                lambda shape, lo, hi: lo + (hi - lo) * cut("uniform", shape))


def _frozen(d: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()))


@functools.lru_cache(maxsize=None)
def _init_program(sizes: tuple):
    import jax

    m = dict(sizes)
    return jax.jit(lambda k: mamba_init(k, m))


def init(key, m: dict):
    """``mamba_init`` as one jitted program, compiled once a process for a
    set of sizes: the driver's weights and the reference's share it."""
    return _init_program(_frozen(m))(key)


@functools.lru_cache(maxsize=None)
def _programs(optimizer: tuple):
    """(zeros like a tree, AdamW's update, leaf norms, the norms of a
    difference of two trees), jitted once a process."""
    import jax
    import jax.numpy as jnp

    o = dict(optimizer)
    return (
        jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p)),
        jax.jit(lambda p, a, b, g, c: reference.adamw_update(p, a, b, g, c,
                                                             o),
                donate_argnums=(0, 1, 2, 3)),
        jax.jit(reference.leaf_norms),
        jax.jit(lambda p, q: reference.leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, p, q))))


def change_norms(params, initial):
    """The norm of every leaf of ``params - initial``, a dict of arrays."""
    return _programs(())[3](params, initial)


def _matmul(matmul_in):
    import jax.numpy as jnp

    q8 = matmul_in or (lambda x: x)
    return q8, lambda a, b: jnp.matmul(q8(a), q8(b), precision="highest")


def _norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def ssm_scan(x, b, c, dt, a, *, matmul_in=None):
    """The per-token recurrence. x [B, T, H, P]; b, c [B, T, N] (one pair
    for every head); dt, a (Delta and Delta A) [B, T, H] -> y [B, T, H, P]
    (without D x)."""
    import jax
    import jax.numpy as jnp

    q8 = matmul_in or (lambda z: z)
    bsz, t, nh, p = x.shape
    n = b.shape[-1]
    block = next(s for s in range(min(t, SCAN_BLOCK), 0, -1) if t % s == 0)

    def token(s, xs):
        x_t, b_t, c_t, dt_t, a_t = xs
        write = (dt_t[..., None, None] * q8(b_t)[:, None, :, None]
                 * q8(x_t)[:, :, None, :])
        s = jnp.exp(a_t)[..., None, None] * s + write
        return s, jnp.einsum("bhnp,bn->bhp", q8(s), q8(c_t),
                             precision="highest")

    @jax.checkpoint
    def some(s, xs):
        return jax.lax.scan(token, s, xs)

    by_block = lambda z: jnp.moveaxis(z, 1, 0).reshape(   # noqa: E731
        t // block, block, *z.shape[:1], *z.shape[2:])
    _, y = jax.lax.scan(some, jnp.zeros((bsz, nh, n, p), jnp.float32),
                        tuple(by_block(z) for z in (x, b, c, dt, a)))
    return jnp.moveaxis(y.reshape(t, bsz, nh, p), 0, 1)


def mamba_mixer(y, lp, m: dict, *, matmul_in=None, fault=None):
    """The Mamba-2 block on normed y [B, T, H], before the residual."""
    import jax
    import jax.numpy as jnp

    _, mm = _matmul(matmul_in)
    bsz, t, _ = y.shape
    nh, p, n, d_in, conv = _dims(m)
    xbcz = mm(y, lp["in_proj"])
    dt = jax.nn.softplus(mm(y, lp["dt_proj"]) + lp["dt_bias"])
    a = dt * -jnp.exp(lp["a_log"])
    if fault == "no_decay":
        a = jnp.zeros_like(a)
    taps = lp["conv"]
    k = taps.shape[0]
    padded = jnp.pad(xbcz[..., :conv], ((0, 0), (k - 1, 0), (0, 0)))
    pre = sum(padded[:, j:j + t] * taps[j] for j in range(k))
    if fault != "no_conv_bias":
        pre = pre + lp["conv_bias"]
    xbc = jax.nn.silu(pre)
    x = xbc[..., :d_in].reshape(bsz, t, nh, p)
    out = ssm_scan(x, xbc[..., d_in:d_in + n], xbc[..., d_in + n:], dt, a,
                   matmul_in=matmul_in)
    if fault != "no_skip":
        out = out + lp["d"][:, None] * x
    out = out.reshape(bsz, t, d_in)
    gate = jax.nn.silu(xbcz[..., conv:])
    eps = m["rms_norm_eps"]
    if fault == "norm_then_gate":
        out = _norm(out, lp["out_norm"], eps) * gate
    else:
        out = _norm(out * gate, lp["out_norm"], eps)
    return mm(out, lp["out_proj"])


def attention_mixer(y, lp, m: dict, *, matmul_in=None, fault=None):
    """Causal attention without positions on normed y [B, T, H], one head
    at a time, scores times ``attention_multiplier``."""
    import jax
    import jax.numpy as jnp

    q8, mm = _matmul(matmul_in)
    bsz, t, h = y.shape
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    d = h // nh
    mask = jnp.tril(jnp.ones((t, t), bool))
    q = mm(y, lp["wq"]).reshape(bsz, t, nh, d)
    k = mm(y, lp["wk"]).reshape(bsz, t, nkv, d)
    v = mm(y, lp["wv"]).reshape(bsz, t, nkv, d)

    @jax.checkpoint
    def attend(qkv):
        q_h, k_h, v_h = qkv
        s = jnp.einsum("btd,bsd->bts", q8(q_h), q8(k_h),
                       precision="highest") * m["attention_multiplier"]
        pr = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", q8(pr), q8(v_h),
                          precision="highest")

    heads = lambda a, r: jnp.moveaxis(jnp.repeat(a, r, axis=2), 2, 0)  # noqa
    o = jax.lax.map(attend, (heads(q, 1), heads(k, nh // nkv),
                             heads(v, nh // nkv)))
    return mm(jnp.moveaxis(o, 0, 2).reshape(bsz, t, nh * d), lp["wo"])


def mamba_logits(params, tokens, m: dict, *, matmul_in=None, fault=None,
                 head=None):
    """The plain forward pass in float32: logits [B, T, vocab]. ``head``:
    the output head's [vocab, H], the embedding where None (tied). The
    layers run in ``layer_types``' order: a period's Mamba layers in one
    ``lax.scan``, the attention layer before the Mamba layer at its
    place."""
    import jax
    import jax.numpy as jnp

    _, mm = _matmul(matmul_in)
    eps, r = m["rms_norm_eps"], m["residual_multiplier"]
    _, n_mamba, place = layout(m)
    kw = dict(matmul_in=matmul_in, fault=fault)

    def layer(mixer):
        def run(x, lp):
            x = x + r * mixer(_norm(x, lp["mixer_norm"], eps), lp, m, **kw)
            y = _norm(x, lp["mlp_norm"], eps)
            return x + r * mm(jax.nn.silu(mm(y, lp["w_gate"]))
                              * mm(y, lp["w_up"]), lp["w_down"])
        return run

    mamba, attn = layer(mamba_mixer), layer(attention_mixer)

    @jax.checkpoint
    def mamba_layer(x, lp, i, attn_lp):
        """The period's Mamba layer i, after the attention layer where that
        is its place; recomputed in the backward pass."""
        x = jax.lax.cond(i == place, lambda x: attn(x, attn_lp),
                         lambda x: x, x)
        return mamba(x, lp)

    at = lambda tree, i: {k: v[i] for k, v in tree.items()}   # noqa: E731
    x = params["embed"][tokens] * m["embedding_multiplier"]
    for period in range(len(params["attention"]["wq"])):
        attn_lp = at(params["attention"], period)
        x, _ = jax.lax.scan(
            lambda x, xs: (mamba_layer(x, *xs, attn_lp), None), x,
            (at(params["mamba"], period), jnp.arange(n_mamba)))
        if place == n_mamba:            # the attention layer ends the period
            x = jax.checkpoint(attn)(x, attn_lp)
    x = _norm(x, params["final_norm"], eps)
    head = params["embed"] if head is None else head
    return mm(x, head.T) / m["logits_scaling"]


def mamba_loss(params, tokens, m: dict, *, matmul_in=None, keep=None,
               fault=None, head=None):
    """Next-token cross-entropy of the plain forward pass in float32.
    ``matmul_in`` rounds every matmul operand (the low-precision control);
    ``keep`` = number of leading positions whose loss counts (the half-batch
    fault); ``fault``: one of ``FAULTS``; ``head`` as ``mamba_logits``
    takes it."""
    import jax
    import jax.numpy as jnp

    logits = mamba_logits(params, tokens, m, matmul_in=matmul_in,
                          fault=fault, head=head)[:, :-1]
    targets = tokens[:, 1:]
    nll = (jax.nn.logsumexp(logits, axis=-1) -
           jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0])
    if keep is not None:
        nll = nll[:, :keep]
    return jnp.mean(nll)


def train_reference(seed: int, m: dict, o: dict, tokens, steps: int, *,
                    matmul_in=None, keep=None, fault=None) -> dict:
    """Follows the first ``steps`` steps from the seed: each step's loss,
    the per-leaf norm of the first gradient and the per-leaf norm of the
    parameters' change after the last step. Gradients and updates are
    separate donated programs, and between updates the moments live on the
    host, so that the gradient program has the device to itself."""
    import jax

    key = reference.seed_key(seed)
    params = init(key, m)
    mu = nu = None
    with jax.default_matmul_precision("highest"):
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p, t: mamba_loss(p, t, m, matmul_in=matmul_in, keep=keep,
                                    fault=fault)))
        zeros, update, norms, _ = _programs(_frozen(o))
        losses, grad_norms = [], None
        for i in range(steps):
            loss, grads = grad_fn(params, tokens[i])
            losses.append(float(loss))
            if i == 0:
                grad_norms = {k: float(v) for k, v in norms(grads).items()}
            moments = (zeros(params), zeros(params)) if mu is None else \
                jax.device_put((mu, nu))
            params, *moments = update(params, *moments, grads, i + 1)
            if i + 1 < steps:
                mu, nu = jax.device_get(moments)
            del moments, grads
    # outside the precision's context, which is part of a program's key:
    # the programs the driver compiled before it are found again
    delta_norms = {k: float(v) for k, v in change_norms(
        params, init(key, m)).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms}
