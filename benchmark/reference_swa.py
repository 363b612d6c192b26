"""Plain reference of the window-and-full-attention train step (Laguna-XS.2:
three sliding-window layers of 64 query heads to one full layer of 48, over
8 KV heads of 128, a rope a kind of layer, a per-head sigmoid gate on the
heads' output, a leading dense layer and then sigmoid-routed experts beside
a shared one, one chip's share of the experts) and AdamW, in ``jax.numpy``
float32 at ``highest`` matmul precision. No kernel, no tile, no sorting, no
grouped product: the mask is a comparison of positions, attention is dense
one query head at a time, and every held expert runs on every token,
weighted by the router (zero where it was not selected).

The layer (x [T, hidden]; H query heads of the layer's kind, d = head_dim):

    h = rmsnorm(x; w_in);  q = h W_q [T,H,d];  k = h W_k, v = h W_v [T,8,d]
    g = h W_g [T,H]
    rope(q), rope(k): window layers theta^(-2i/d) on the whole head; full
        layers yarn on the leading half (below), the rest unrotated
    s = q k^T / sqrt(d) where 0 <= i - j (full) or 0 <= i - j < W (window)
    o_h = sigmoid(g_h) softmax(s_h) v;  x <- x + concat(o) W_o
    y = rmsnorm(x; w_post)
    layer 0:  x <- x + swiglu(y)                     (intermediate_size)
    others:   s = sigmoid(y W_r) over all experts, the 8 largest,
              w = s_sel / sum s_sel * scaling
              x <- x + sum_e w_e swiglu_e(y) + swiglu_shared(y)

yarn, one frequency a pair of lanes i < rot/2 (rot = d/2): pos =
theta^(2i/rot); extrap = 1/pos; interp = 1/(factor pos); c(r) = rot
ln(original / (2 pi r)) / (2 ln theta); low = max(floor(c(beta_fast)), 0);
high = min(ceil(c(beta_slow)), rot - 1); ramp = clip((i - low)/(high - low),
0, 1); inv_freq = interp ramp + extrap (1 - ramp); cos and sin times
attention_factor.

Nothing here imports the program (``brpc_tpu``) or takes anything the program
made; ``reference.py``'s helpers (keys, AdamW, norms, the fp8 operand) are
shared. ``m`` is the model's sizes under the names of the published
``config.json``, with ``num_experts`` the experts HELD (``expert_offset``
on) and ``router_experts`` the published count, which the router keeps; the
three per-layer lists are read up to ``num_hidden_layers``.
"""

from __future__ import annotations

import math

import reference

FAULTS = ("no_window", "window_256", "one_rope", "no_yarn_scale",
          "no_attn_gate", "drop_eighth", "no_scaling")
PERIOD = 4      # the tree's: three window layers, then the full one


def _layers(m: dict) -> list:
    """(kind, query heads, mlp kind) of every layer held."""
    n = m["num_hidden_layers"]
    kinds = [{"full_attention": "full", "sliding_attention": "window"}[t]
             for t in m["layer_types"][:n]]
    return list(zip(kinds, m["num_attention_heads_per_layer"][:n],
                    m["mlp_layer_types"][:n]))


def _stacks(m: dict) -> tuple:
    return divmod(m["num_hidden_layers"] - 1, PERIOD)


def _heads(m: dict, kind: str) -> int:
    return next(h for k, h, _ in _layers(m) if k == kind)


def windowed_init(key, m: dict):
    """The weights of a run, from the seed's key, float32, one traced
    function: normal(0, fan_in^-0.5) matrices, norms 1. The tree is the one
    the program's step takes: layer 0 alone (``first``), then window and
    full layers stacked apart by period, [periods, 3, ...] and [periods,
    ...], window layers left over in ``tail``."""
    import jax
    import jax.numpy as jnp

    h, v, d = m["hidden_size"], m["vocab_size"], m["head_dim"]
    kv = m["num_key_value_heads"] * d
    f, fs = m["moe_intermediate_size"], m["shared_expert_intermediate_size"]
    held, inter = m["num_experts"], m["intermediate_size"]
    periods, n_tail = _stacks(m)
    keys = iter(jax.random.split(key, 64))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) \
            * fan_in ** -0.5

    def layer(lead, heads, sparse=True):
        mlp = {
            "router": dense(lead + (h, m["router_experts"]), h),
            "w_gate": dense(lead + (held, h, f), h),
            "w_up": dense(lead + (held, h, f), h),
            "w_down": dense(lead + (held, f, h), f),
            "shared_gate": dense(lead + (h, fs), h),
            "shared_up": dense(lead + (h, fs), h),
            "shared_down": dense(lead + (fs, h), fs),
        } if sparse else {
            "w_gate": dense(lead + (h, inter), h),
            "w_up": dense(lead + (h, inter), h),
            "w_down": dense(lead + (inter, h), inter),
        }
        return {
            **mlp,
            "wq": dense(lead + (h, heads * d), h),
            "wk": dense(lead + (h, kv), h), "wv": dense(lead + (h, kv), h),
            "wg": dense(lead + (h, heads), h),
            "wo": dense(lead + (heads * d, h), heads * d),
            "attn_norm": jnp.ones(lead + (h,), jnp.float32),
            "mlp_norm": jnp.ones(lead + (h,), jnp.float32),
        }

    n_full, n_window = _heads(m, "full"), _heads(m, "window")
    params = {
        "embed": dense((v, h), 1.0),
        "first": layer((), n_full, sparse=False),
        "window": layer((periods, PERIOD - 1), n_window),
        "full": layer((periods,), n_full),
        "final_norm": jnp.ones((h,), jnp.float32),
        "lm_head": dense((h, v), h),
    }
    if n_tail:
        params["tail"] = layer((n_tail,), n_window)
    return params


def _matmul(matmul_in):
    import jax.numpy as jnp

    q8 = matmul_in or (lambda x: x)
    return q8, lambda a, b: jnp.matmul(q8(a), q8(b), precision="highest")


def _norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def yarn_inv_freq(r: dict, rot: int):
    """The blended frequencies of a yarn rope, one a pair of lanes;
    ``r``: the published ``rope_parameters`` group of the layer kind."""
    import jax.numpy as jnp

    theta, factor = float(r["rope_theta"]), float(r["factor"])
    original = r["original_max_position_embeddings"]
    i = jnp.arange(rot // 2, dtype=jnp.float32)
    pos = theta ** (2 * i / rot)

    def c(turns):
        return rot * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(c(r["beta_fast"])), 0)
    high = min(math.ceil(c(r["beta_slow"])), rot - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return 1 / (factor * pos) * ramp + 1 / pos * (1 - ramp)


def _rope(a, r: dict, fault=None):
    """Rotary embedding by the published group ``r``: the leading
    ``partial_rotary_factor`` of the head's lanes as two halves, ``default``
    or ``yarn`` frequencies. a: [B, T, H, D]."""
    import jax.numpy as jnp

    t, d = a.shape[1], a.shape[-1]
    rot = int(d * r["partial_rotary_factor"])
    if r["rope_type"] == "yarn":
        inv_freq = yarn_inv_freq(r, rot)
        scale = 1.0 if fault == "no_yarn_scale" else r["attention_factor"]
    else:
        inv_freq = float(r["rope_theta"]) ** (
            -jnp.arange(0, rot // 2, dtype=jnp.float32) / (rot // 2))
        scale = 1.0
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos = (jnp.cos(ang) * scale)[None, :, None]
    sin = (jnp.sin(ang) * scale)[None, :, None]
    a1, a2 = a[..., :rot // 2], a[..., rot // 2:rot]
    return jnp.concatenate([a1 * cos - a2 * sin, a1 * sin + a2 * cos,
                            a[..., rot:]], axis=-1)


def attention_block(x, lp, m: dict, kind: str, *, matmul_in=None,
                    fault=None):
    """The attention block of a ``full`` or ``window`` layer with its
    residual. x: [B, T, H]. ``fault``: ``no_window`` runs the window layers
    causal, ``window_256`` halves their window (256 of 512), ``one_rope`` gives them the
    full layers' rope, ``no_yarn_scale`` leaves the attention factor out,
    ``no_attn_gate`` the heads' sigmoid gate."""
    import jax
    import jax.numpy as jnp

    q8, mm = _matmul(matmul_in)
    b, t, _ = x.shape
    nkv, d = m["num_key_value_heads"], m["head_dim"]
    nh = lp["wg"].shape[-1]
    ropes = m["rope_parameters"]
    r = ropes["full_attention"] if kind == "full" or fault == "one_rope" \
        else ropes["sliding_attention"]
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    mask = i - j >= 0
    if kind == "window" and fault != "no_window":
        mask &= i - j < m["sliding_window"] // (
            2 if fault == "window_256" else 1)
    y = _norm(x, lp["attn_norm"], m["rms_norm_eps"])
    q = _rope(mm(y, lp["wq"]).reshape(b, t, nh, d), r, fault)
    k = _rope(mm(y, lp["wk"]).reshape(b, t, nkv, d), r, fault)
    v = mm(y, lp["wv"]).reshape(b, t, nkv, d)
    gate = mm(y, lp["wg"])

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

    heads = lambda a, rep: jnp.moveaxis(jnp.repeat(a, rep, axis=2), 2, 0)  # noqa
    o = jax.lax.map(attend, (heads(q, 1), heads(k, nh // nkv),
                             heads(v, nh // nkv)))
    o = jnp.moveaxis(o, 0, 2)                               # [b, t, nh, d]
    if fault != "no_attn_gate":
        o = o * jax.nn.sigmoid(gate)[..., None]
    return x + mm(o.reshape(b, t, nh * d), lp["wo"])


def moe_mlp(y, lp, m: dict, *, matmul_in=None, fault=None):
    """The expert layer's MLP on normed tokens y [N, H]: the share that
    experts ``expert_offset`` .. + ``num_experts`` give, plus the shared
    expert. Returns (result, selected experts [N, k]). ``fault``:
    ``drop_eighth`` leaves out the last selected expert's contribution,
    ``no_scaling`` the routed scaling factor."""
    import jax
    import jax.numpy as jnp

    _, mm = _matmul(matmul_in)
    s = jax.nn.sigmoid(mm(y, lp["router"]))
    _, selected = jax.lax.top_k(s, m["num_experts_per_tok"])
    w = jnp.take_along_axis(s, selected, axis=1)
    w = w / jnp.sum(w, axis=1, keepdims=True)
    if fault != "no_scaling":
        w = w * m["moe_routed_scaling_factor"]
    if fault == "drop_eighth":
        w = w.at[:, -1].set(0.0)

    def swiglu(gate, up, down):
        return mm(jax.nn.silu(mm(y, gate)) * mm(y, up), down)

    @jax.checkpoint
    def one_expert(out, args):
        e, gate, up, down = args
        weight = jnp.sum(jnp.where(selected == e, w, 0.0), axis=1)
        return out + weight[:, None] * swiglu(gate, up, down), None

    out = swiglu(lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    experts = m.get("expert_offset", 0) + jnp.arange(m["num_experts"])
    out, _ = jax.lax.scan(one_expert, out, (experts, lp["w_gate"],
                                            lp["w_up"], lp["w_down"]))
    return out, selected


def windowed_loss(params, tokens, m: dict, *, matmul_in=None, keep=None,
                  fault=None):
    """(next-token cross-entropy of the plain forward pass in float32, the
    experts each expert layer selected [L - 1, B*T, k], layers in their
    order). ``matmul_in`` rounds every matmul operand (the low-precision
    control); ``keep`` = number of leading positions whose loss counts (the
    half-batch fault); ``fault``: one of ``FAULTS``."""
    import jax
    import jax.numpy as jnp

    _, mm = _matmul(matmul_in)
    eps = m["rms_norm_eps"]
    b, t = tokens.shape
    kw = dict(matmul_in=matmul_in, fault=fault)
    periods, _ = _stacks(m)

    def layer(kind, mlp):
        @jax.checkpoint
        def run(x, lp):
            x = attention_block(x, lp, m, kind, **kw)
            y = _norm(x, lp["mlp_norm"], eps)
            if mlp == "dense":
                return x + mm(jax.nn.silu(mm(y, lp["w_gate"]))
                              * mm(y, lp["w_up"]), lp["w_down"]), None
            out, selected = moe_mlp(y.reshape(b * t, -1), lp, m, **kw)
            return x + out.reshape(x.shape), selected
        return run

    at = lambda tree, *i: {k: v[i] for k, v in tree.items()}   # noqa: E731
    x = params["embed"][tokens]
    selections = []
    for i, (kind, _, mlp) in enumerate(_layers(m)):
        period, place = divmod(i - 1, PERIOD)
        if i == 0:
            lp = params["first"]
        elif period >= periods:
            lp = at(params["tail"], place)
        elif place < PERIOD - 1:
            lp = at(params["window"], period, place)
        else:
            lp = at(params["full"], period)
        x, selected = layer(kind, mlp)(x, lp)
        if selected is not None:
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
    ``reference_gdn.train_reference`` does: each step's loss, the per-leaf
    norm of the first gradient, the per-leaf norm of the parameters' change
    after the last step, and the first step's selections. Gradients and
    updates are separate donated programs, and between updates the moments
    live on the host, so that the gradient program has the device to
    itself."""
    import jax
    import jax.numpy as jnp

    key = reference.seed_key(seed)
    params = jax.jit(lambda k: windowed_init(k, m))(key)
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
    mu = nu = None
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t: windowed_loss(p, t, m, matmul_in=matmul_in, keep=keep,
                                   fault=fault), has_aux=True))
    update = jax.jit(
        lambda p, a, b, g, c: reference.adamw_update(p, a, b, g, c, o),
        donate_argnums=(0, 1, 2, 3))
    norms = jax.jit(reference.leaf_norms)
    delta = jax.jit(lambda p, k: reference.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, windowed_init(k, m))))
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
