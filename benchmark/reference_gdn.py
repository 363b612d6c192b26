"""Plain reference of the hybrid linear-attention train step
(Qwen3-Next-80B-A3B: three Gated DeltaNet layers to one gated full-attention
layer, softmax-routed experts beside a gated shared expert, one chip's share
of the experts) and AdamW, in ``jax.numpy`` float32 at ``highest`` matmul
precision. No kernel, no chunked form, no sorting, no grouped product: the
gated delta rule is the published per-token recurrence

    S <- exp(g_t) S;  delta = beta_t (v_t - S^T k_t);  S <- S + k_t delta^T
    o_t = S^T q_t

(checkpointed in blocks of positions, since a per-token history of the
state would be 17 GB at 8,192 tokens), the convolution is its four shifted
adds, attention is dense one head at a time, and every held expert runs on
every token, weighted by the router (zero where it was not selected).

Nothing here imports the program (``brpc_tpu``) or takes anything the program
made; ``reference.py``'s helpers (keys, AdamW, norms, the fp8 operand) are
shared. ``m`` is the model's sizes under the names of the published
``config.json``, with ``num_experts`` the experts HELD (``expert_offset``
on) and ``router_experts`` the published count, which the router keeps.

Departures from the published model, the same as the program's: ``W_qkvz``'s
columns lie q | k | v | z and ``W_ba``'s b | a, not grouped by key head (a
permutation of random weights); no multi-token prediction; no auxiliary
loss; ``intermediate_size`` unused (no dense layer).
"""

from __future__ import annotations

import reference

RULE_BLOCK = 128        # positions of the recurrence kept at once
FAULTS = ("no_decay", "no_delta", "no_out_gate", "drop_tenth",
          "no_shared_gate")


def _dims(m: dict):
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    return hk, hv, dk, dv


def hybrid_init(key, m: dict):
    """The weights of a run, from the seed's key, float32, one traced
    function: normal(0, fan_in^-0.5) matrices and convolution taps, the
    zero-centred norms 0, the gated norm's weight 1, ``a_log`` log U(0, 16),
    ``dt_bias`` 1. The tree is the one the program's step takes: linear and
    full layers stacked apart, [periods, interval - 1, ...] and
    [periods, ...]."""
    import jax
    import jax.numpy as jnp

    h, v = m["hidden_size"], m["vocab_size"]
    interval = m["full_attention_interval"]
    periods = m["num_hidden_layers"] // interval
    hk, hv, dk, dv = _dims(m)
    key_dim, value_dim = hk * dk, hv * dv
    nh, nkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    f, held = m["moe_intermediate_size"], m["num_experts"]
    fs, taps = m["shared_expert_intermediate_size"], m["linear_conv_kernel_dim"]
    keys = iter(jax.random.split(key, 48))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) \
            * fan_in ** -0.5

    def experts(lead):
        return {
            "router": dense(lead + (h, m["router_experts"]), h),
            "w_gate": dense(lead + (held, h, f), h),
            "w_up": dense(lead + (held, h, f), h),
            "w_down": dense(lead + (held, f, h), f),
            "shared_gate": dense(lead + (h, fs), h),
            "shared_up": dense(lead + (h, fs), h),
            "shared_down": dense(lead + (fs, h), fs),
            "shared_w": dense(lead + (h,), h),
            "mixer_norm": jnp.zeros(lead + (h,), jnp.float32),
            "mlp_norm": jnp.zeros(lead + (h,), jnp.float32),
        }

    lin = (periods, interval - 1)
    return {
        "embed": dense((v, h), 1.0),
        "linear": {
            **experts(lin),
            "w_qkvz": dense(lin + (h, 2 * key_dim + 2 * value_dim), h),
            "w_ba": dense(lin + (h, 2 * hv), h),
            "conv": dense(lin + (taps, 2 * key_dim + value_dim), taps),
            "w_out": dense(lin + (value_dim, h), value_dim),
            "a_log": jnp.log(jax.random.uniform(
                next(keys), lin + (hv,), jnp.float32, 1e-3, 16.0)),
            "dt_bias": jnp.ones(lin + (hv,), jnp.float32),
            "out_norm": jnp.ones(lin + (dv,), jnp.float32),
        },
        "full": {
            **experts((periods,)),
            "wq": dense((periods, h, 2 * nh * d), h),
            "wk": dense((periods, h, nkv * d), h),
            "wv": dense((periods, h, nkv * d), h),
            "wo": dense((periods, nh * d, h), nh * d),
            "q_norm": jnp.zeros((periods, d), jnp.float32),
            "k_norm": jnp.zeros((periods, d), jnp.float32),
        },
        "final_norm": jnp.zeros((h,), jnp.float32),
        "lm_head": dense((h, v), h),
    }


def _matmul(matmul_in):
    import jax.numpy as jnp

    q8 = matmul_in or (lambda x: x)
    return q8, lambda a, b: jnp.matmul(q8(a), q8(b), precision="highest")


def _norm(x, w, eps):
    """RMSNorm with the zero-centred weight (1 + w)."""
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def delta_rule(q, k, v, g, beta, *, matmul_in=None, fault=None):
    """The per-token recurrence. q, k [B, T, Hv, dk] (every value head its
    own copy), v [B, T, Hv, dv], g, beta [B, T, Hv] -> o [B, T, Hv, dv].
    ``fault``: ``no_decay`` takes g = 0, ``no_delta`` writes beta v without
    taking what the state already holds (plain gated linear attention)."""
    import jax
    import jax.numpy as jnp

    q8 = matmul_in or (lambda x: x)
    b, t, hv, dk = q.shape
    dv = v.shape[-1]
    block = next(c for c in range(min(t, RULE_BLOCK), 0, -1) if t % c == 0)
    if fault == "no_decay":
        g = jnp.zeros_like(g)

    def token(s, x):
        q_t, k_t, v_t, g_t, beta_t = x
        s = jnp.exp(g_t)[..., None, None] * s
        held = 0.0 if fault == "no_delta" else jnp.einsum(
            "bhkv,bhk->bhv", q8(s), q8(k_t), precision="highest")
        delta = beta_t[..., None] * (v_t - held)
        s = s + q8(k_t)[..., :, None] * q8(delta)[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", q8(s), q8(q_t),
                             precision="highest")

    @jax.checkpoint
    def some(s, xs):
        return jax.lax.scan(token, s, xs)

    by_block = lambda a: jnp.moveaxis(a, 1, 0).reshape(   # noqa: E731
        t // block, block, *a.shape[:1], *a.shape[2:])
    _, o = jax.lax.scan(some, jnp.zeros((b, hv, dk, dv), jnp.float32),
                        tuple(by_block(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(t, b, hv, dv), 0, 1)


def gated_delta_net(x, lp, m: dict, *, matmul_in=None, fault=None):
    """The linear-attention block with its residual. x: [B, T, H]."""
    import jax
    import jax.numpy as jnp

    _, mm = _matmul(matmul_in)
    b, t, _ = x.shape
    hk, hv, dk, dv = _dims(m)
    key_dim, value_dim = hk * dk, hv * dv
    y = _norm(x, lp["mixer_norm"], m["rms_norm_eps"])
    qkvz, ba = mm(y, lp["w_qkvz"]), mm(y, lp["w_ba"])
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(lp["a_log"]) * jax.nn.softplus(ba[..., hv:] + lp["dt_bias"])
    mixed = qkvz[..., :2 * key_dim + value_dim]
    taps = lp["conv"]
    n = taps.shape[0]
    padded = jnp.pad(mixed, ((0, 0), (n - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + t] * taps[j] for j in range(n)))

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                                 + 1e-6)

    spread = lambda a: jnp.repeat(a, hv // hk, axis=2)   # noqa: E731
    q = spread(unit(qkv[..., :key_dim].reshape(b, t, hk, dk))) * dk ** -0.5
    k = spread(unit(qkv[..., key_dim:2 * key_dim].reshape(b, t, hk, dk)))
    v = qkv[..., 2 * key_dim:].reshape(b, t, hv, dv)
    o = delta_rule(q, k, v, g, beta, matmul_in=matmul_in, fault=fault)
    z = qkvz[..., 2 * key_dim + value_dim:].reshape(b, t, hv, dv)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + m["rms_norm_eps"]) * lp["out_norm"]
    o = o * jax.nn.silu(z)
    return x + mm(o.reshape(b, t, value_dim), lp["w_out"])


def gated_attention(x, lp, m: dict, *, matmul_in=None, fault=None):
    """The full-attention block with its residual; ``no_out_gate`` leaves
    the sigmoid gate on the heads' output out."""
    import jax
    import jax.numpy as jnp

    q8, mm = _matmul(matmul_in)
    b, t, _ = x.shape
    nh, nkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    rot = int(d * m["partial_rotary_factor"])
    eps = m["rms_norm_eps"]
    mask = jnp.tril(jnp.ones((t, t), bool))
    y = _norm(x, lp["mixer_norm"], eps)
    q_gate = mm(y, lp["wq"]).reshape(b, t, nh, 2 * d)
    q = _norm(q_gate[..., :d], lp["q_norm"], eps)
    gate = q_gate[..., d:].reshape(b, t, nh * d)
    k = _norm(mm(y, lp["wk"]).reshape(b, t, nkv, d), lp["k_norm"], eps)
    v = mm(y, lp["wv"]).reshape(b, t, nkv, d)

    def rotate(a):            # halves of the first ``rot`` dims are pairs
        freqs = m["rope_theta"] ** (
            -jnp.arange(0, rot // 2, dtype=jnp.float32) / (rot // 2))
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        a1, a2 = a[..., :rot // 2], a[..., rot // 2:rot]
        return jnp.concatenate([a1 * cos - a2 * sin, a1 * sin + a2 * cos,
                                a[..., rot:]], axis=-1)

    q, k = rotate(q), rotate(k)

    @jax.checkpoint
    def attend(qkv):
        """One query head against its KV head: [b,t,d] each; one head's
        float32 scores are live at a time."""
        q_h, k_h, v_h = qkv
        s = jnp.einsum("btd,bsd->bts", q8(q_h), q8(k_h),
                       precision="highest") * d ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", q8(p), q8(v_h),
                          precision="highest")

    heads = lambda a, r: jnp.moveaxis(jnp.repeat(a, r, axis=2), 2, 0)  # noqa
    o = jax.lax.map(attend, (heads(q, 1), heads(k, nh // nkv),
                             heads(v, nh // nkv)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, t, nh * d)
    if fault != "no_out_gate":
        o = o * jax.nn.sigmoid(gate)
    return x + mm(o, lp["wo"])


def moe_mlp(y, lp, m: dict, *, matmul_in=None, fault=None):
    """The expert layer's MLP on normed tokens y [N, H]: the share that
    experts ``expert_offset`` .. + ``num_experts`` give, plus the gated
    shared expert. Returns (result, selected experts [N, k]). ``fault``:
    ``drop_tenth`` leaves out the last selected expert's contribution,
    ``no_shared_gate`` the shared expert's sigmoid gate."""
    import jax
    import jax.numpy as jnp

    _, mm = _matmul(matmul_in)
    p = jax.nn.softmax(mm(y, lp["router"]), axis=-1)
    _, selected = jax.lax.top_k(p, m["num_experts_per_tok"])
    w = jnp.take_along_axis(p, selected, axis=1)
    w = w / jnp.sum(w, axis=1, keepdims=True)
    if fault == "drop_tenth":
        w = w.at[:, -1].set(0.0)

    def swiglu(gate, up, down):
        return mm(jax.nn.silu(mm(y, gate)) * mm(y, up), down)

    @jax.checkpoint
    def one_expert(out, args):
        e, gate, up, down = args
        weight = jnp.sum(jnp.where(selected == e, w, 0.0), axis=1)
        return out + weight[:, None] * swiglu(gate, up, down), None

    out = swiglu(lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    if fault != "no_shared_gate":
        out = out * jax.nn.sigmoid(mm(y, lp["shared_w"]))[:, None]
    experts = m.get("expert_offset", 0) + jnp.arange(m["num_experts"])
    out, _ = jax.lax.scan(one_expert, out, (experts, lp["w_gate"],
                                            lp["w_up"], lp["w_down"]))
    return out, selected


def hybrid_loss(params, tokens, m: dict, *, matmul_in=None, keep=None,
                fault=None):
    """(next-token cross-entropy of the plain forward pass in float32, the
    experts each layer selected [L, B*T, k], layers in their order).
    ``matmul_in`` rounds every matmul operand (the low-precision control);
    ``keep`` = number of leading positions whose loss counts (the
    half-batch fault); ``fault``: one of ``FAULTS``."""
    import jax
    import jax.numpy as jnp

    _, mm = _matmul(matmul_in)
    eps = m["rms_norm_eps"]
    b, t = tokens.shape
    interval = m["full_attention_interval"]
    kw = dict(matmul_in=matmul_in, fault=fault)

    def layer(mixer):
        @jax.checkpoint
        def run(x, lp):
            x = mixer(x, lp, m, **kw)
            y = _norm(x, lp["mlp_norm"], eps).reshape(b * t, -1)
            out, selected = moe_mlp(y, lp, m, **kw)
            return x + out.reshape(x.shape), selected
        return run

    linear, full = layer(gated_delta_net), layer(gated_attention)
    at = lambda tree, *i: {k: v[i] for k, v in tree.items()}   # noqa: E731
    x = params["embed"][tokens]
    selections = []
    for i in range(m["num_hidden_layers"]):
        period, place = divmod(i, interval)
        if (i + 1) % interval:
            x, selected = linear(x, at(params["linear"], period, place))
        else:
            x, selected = full(x, at(params["full"], period))
        selections.append(selected)
    logits = mm(_norm(x, params["final_norm"], eps), params["lm_head"])[:, :-1]
    targets = tokens[:, 1:]
    nll = (jax.nn.logsumexp(logits, axis=-1) -
           jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0])
    if keep is not None:
        nll = nll[:, :keep]
    return jnp.mean(nll), jnp.stack(selections)


def train_reference(seed: int, m: dict, o: dict, tokens, steps: int, *,
                    matmul_in=None, keep=None, fault=None) -> dict:
    """Follows the first ``steps`` steps from the seed, as
    ``reference_dsv3.train_reference`` does: each step's loss, the per-leaf
    norm of the first gradient, the per-leaf norm of the parameters' change
    after the last step, and the first step's selections. Gradients and
    updates are separate donated programs, and between updates the moments
    live on the host, so that the gradient program has the device to
    itself."""
    import jax
    import jax.numpy as jnp

    key = reference.seed_key(seed)
    params = jax.jit(lambda k: hybrid_init(k, m))(key)
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
    mu = nu = None
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t: hybrid_loss(p, t, m, matmul_in=matmul_in, keep=keep,
                                 fault=fault), has_aux=True))
    update = jax.jit(
        lambda p, a, b, g, c: reference.adamw_update(p, a, b, g, c, o),
        donate_argnums=(0, 1, 2, 3))
    norms = jax.jit(reference.leaf_norms)
    delta = jax.jit(lambda p, k: reference.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, hybrid_init(k, m))))
    losses, grad_norms, selected = [], None, None
    for i in range(steps):
        (loss, chosen), grads = grad_fn(params, tokens[i])
        losses.append(float(loss))
        if i == 0:
            grad_norms = {k: float(v) for k, v in norms(grads).items()}
            selected = chosen
        moments = (zeros(params), zeros(params)) if mu is None else \
            jax.device_put((mu, nu))
        params, *moments = update(params, *moments, grads, i + 1)
        if i + 1 < steps:
            mu, nu = jax.device_get(moments)
        del moments, grads
    delta_norms = {k: float(v) for k, v in delta(params, key).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms, "selected": selected}
