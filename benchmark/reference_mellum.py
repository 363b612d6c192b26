"""Plain reference of the Mellum2 train step (JetBrains/Mellum2-12B-A2.5B:
three sliding-window layers of 1,024 positions to one full layer, 32 query
heads over 4 KV heads of 128 on every layer, a per-head RMSNorm on q and on
k, a rope a kind of layer, every layer's MLP 64 softmax-routed experts,
top-8, with no shared expert; one chip's share of the experts) and AdamW, in
``jax.numpy`` float32 at ``highest`` matmul precision. No kernel, no tile,
no sorting, no grouped product: the mask is a comparison of positions,
attention is dense one query head at a time, and every held expert runs on
every token, weighted by the router (zero where it was not selected).

The layer (x [T, hidden]; H = 32 query heads, d = head_dim):

    h = rmsnorm(x; w_in);  q = h W_q [T,H,d];  k = h W_k, v = h W_v [T,4,d]
    q <- rmsnorm_d(q) w_q;  k <- rmsnorm_d(k) w_k      (per head, over d)
    rope(q), rope(k) on all d lanes, halves rotated: window layers
        theta^(-2i/d); full layers yarn (below)
    s = q k^T / sqrt(d) where 0 <= i - j (full) or 0 <= i - j < W (window)
    x <- x + concat_h(softmax(s_h) v) W_o
    y = rmsnorm(x; w_post)
    p = softmax(y W_r) over all 64 experts, the 8 largest,
    w = p_sel / sum p_sel
    x <- x + sum_e w_e swiglu_e(y)              (nothing else is added)

yarn, one frequency a pair of lanes i < rot/2 (rot = d here): pos =
theta^(2i/rot); extrap = 1/pos; interp = 1/(factor pos); c(r) = rot
ln(original / (2 pi r)) / (2 ln theta); low = max(floor(c(beta_fast)), 0);
high = min(ceil(c(beta_slow)), rot - 1); ramp = clip((i - low)/(high - low),
0, 1); inv_freq = interp ramp + extrap (1 - ramp); cos and sin times
attention_factor.

Departures from the published description (the catalog row of
``model-configs``; the config's keys are Qwen3-MoE's with per-layer tables):
no multi-token-prediction head (``described_as`` names one, the config has
no key for it) and no auxiliary balance loss: the loss is next-token
cross-entropy alone; ``intermediate_size`` (7,168) belongs to dense layers,
of which ``mlp_layer_types`` has none; a ``rope_parameters`` group with no
``partial_rotary_factor`` turns the whole head.

Nothing here imports the program (``brpc_tpu``) or takes anything the program
made; ``reference.py``'s helpers (keys, AdamW, norms, the fp8 operand) are
shared, and so is what ``reference_swa.py`` writes letter for letter the
same (the rounded matmul, RMSNorm, the yarn frequencies). ``m`` is the model's sizes under the names of the published
``config.json``, with ``num_experts`` the experts HELD (``expert_offset``
on) and ``router_experts`` the published count, which the router keeps; the
two per-layer lists are read up to ``num_hidden_layers``. What the absent
experts would add is left out, as the program leaves it out.
"""

from __future__ import annotations

import reference
from reference_swa import _matmul, _norm, yarn_inv_freq

FAULTS = ("window_512", "sigmoid_router", "no_qk_norm", "yarn_half_head",
          "full_first", "drop_eighth")


def _kinds(m: dict) -> list:
    """``full`` or ``window`` for every layer held."""
    n = m["num_hidden_layers"]
    if m["mlp_layer_types"][:n] != ["sparse"] * n:
        raise ValueError("every layer of this model is sparse")
    return [{"full_attention": "full", "sliding_attention": "window"}[t]
            for t in m["layer_types"][:n]]


def _layout(m: dict) -> tuple:
    """(window layers a period, whole periods, window layers left over):
    the tree's stacks, periods of window layers ended by a full layer."""
    kinds = _kinds(m)
    per = kinds.index("full")
    periods, n_tail = divmod(len(kinds), per + 1)
    if kinds != (["window"] * per + ["full"]) * periods \
            + ["window"] * n_tail:
        raise ValueError(f"not periods of {per} window layers and a full "
                         f"one, then window layers: {kinds}")
    return per, periods, n_tail


def mellum_init(key, m: dict):
    """The weights of a run, from the seed's key, float32, one traced
    function: normal(0, fan_in^-0.5) matrices, norms 1. The tree is the one
    the program's step takes: window and full layers stacked apart by
    period, [periods, 3, ...] and [periods, ...], window layers left over
    in ``tail``."""
    import jax
    import jax.numpy as jnp

    h, v, d = m["hidden_size"], m["vocab_size"], m["head_dim"]
    q_out, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
    f, held = m["moe_intermediate_size"], m["num_experts"]
    per, periods, n_tail = _layout(m)
    keys = iter(jax.random.split(key, 32))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) \
            * fan_in ** -0.5

    def layer(lead):
        return {
            "router": dense(lead + (h, m["router_experts"]), h),
            "w_gate": dense(lead + (held, h, f), h),
            "w_up": dense(lead + (held, h, f), h),
            "w_down": dense(lead + (held, f, h), f),
            "wq": dense(lead + (h, q_out), h),
            "wk": dense(lead + (h, kv), h), "wv": dense(lead + (h, kv), h),
            "wo": dense(lead + (q_out, h), q_out),
            "attn_norm": jnp.ones(lead + (h,), jnp.float32),
            "mlp_norm": jnp.ones(lead + (h,), jnp.float32),
            "q_norm": jnp.ones(lead + (d,), jnp.float32),
            "k_norm": jnp.ones(lead + (d,), jnp.float32),
        }

    params = {
        "embed": dense((v, h), 1.0),
        "window": layer((periods, per)),
        "full": layer((periods,)),
        "final_norm": jnp.ones((h,), jnp.float32),
        "lm_head": dense((h, v), h),
    }
    if n_tail:
        params["tail"] = layer((n_tail,))
    return params


def _rope(a, r: dict, rotary_factor: float):
    """Rotary embedding by the published group ``r``: the leading
    ``rotary_factor`` of the head's lanes as two halves, ``default`` or
    ``yarn`` frequencies. a: [B, T, H, D]."""
    import jax.numpy as jnp

    t, d = a.shape[1], a.shape[-1]
    rot = int(d * rotary_factor)
    if r["rope_type"] == "yarn":
        inv_freq, scale = yarn_inv_freq(r, rot), r["attention_factor"]
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
    residual. x: [B, T, H]. ``fault``: ``window_512`` halves the window
    layers' window (512 of 1,024), ``no_qk_norm`` leaves the two per-head
    norms out, ``yarn_half_head`` turns only the leading half of a full
    layer's heads (Laguna-XS.2's share)."""
    import jax
    import jax.numpy as jnp

    q8, mm = _matmul(matmul_in)
    b, t, _ = x.shape
    nh, nkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    r = m["rope_parameters"][
        "full_attention" if kind == "full" else "sliding_attention"]
    rotary = r.get("partial_rotary_factor", 1.0)
    if kind == "full" and fault == "yarn_half_head":
        rotary = 0.5
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    mask = i - j >= 0
    if kind == "window":
        mask &= i - j < m["sliding_window"] // (
            2 if fault == "window_512" else 1)
    y = _norm(x, lp["attn_norm"], m["rms_norm_eps"])
    q = mm(y, lp["wq"]).reshape(b, t, nh, d)
    k = mm(y, lp["wk"]).reshape(b, t, nkv, d)
    v = mm(y, lp["wv"]).reshape(b, t, nkv, d)
    if fault != "no_qk_norm":
        q = _norm(q, lp["q_norm"], m["rms_norm_eps"])
        k = _norm(k, lp["k_norm"], m["rms_norm_eps"])
    q, k = _rope(q, r, rotary), _rope(k, r, rotary)

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
    return x + mm(o.reshape(b, t, nh * d), lp["wo"])


def moe_mlp(y, lp, m: dict, *, matmul_in=None, fault=None):
    """The expert layer's MLP on normed tokens y [N, H]: the share that
    experts ``expert_offset`` .. + ``num_experts`` give, and nothing beside
    it. Returns (result, selected experts [N, k]). ``fault``:
    ``sigmoid_router`` scores by sigmoid (Laguna-XS.2's router without its
    scaling), ``drop_eighth`` leaves out the last selected expert's
    contribution."""
    import jax
    import jax.numpy as jnp

    _, mm = _matmul(matmul_in)
    logits = mm(y, lp["router"])
    p = jax.nn.sigmoid(logits) if fault == "sigmoid_router" \
        else jax.nn.softmax(logits, axis=-1)
    _, selected = jax.lax.top_k(p, m["num_experts_per_tok"])
    w = jnp.take_along_axis(p, selected, axis=1)
    w = w / jnp.sum(w, axis=1, keepdims=True)
    if fault == "drop_eighth":
        w = w.at[:, -1].set(0.0)

    @jax.checkpoint
    def one_expert(out, args):
        e, gate, up, down = args
        weight = jnp.sum(jnp.where(selected == e, w, 0.0), axis=1)
        return out + weight[:, None] * mm(
            jax.nn.silu(mm(y, gate)) * mm(y, up), down), None

    experts = m.get("expert_offset", 0) + jnp.arange(m["num_experts"])
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y),
                          (experts, lp["w_gate"], lp["w_up"], lp["w_down"]))
    return out, selected


def mellum_logits(params, tokens, m: dict, *, matmul_in=None, fault=None):
    """(logits [B, T, vocab] of the plain forward pass in float32, the
    experts each layer selected [L, B*T, k], layers in their order).
    ``fault``: one of ``FAULTS``; ``full_first`` runs the layers' KINDS in
    Laguna-XS.2's order, full, window, window, window, each layer on its
    own weights."""
    import jax
    import jax.numpy as jnp

    _, mm = _matmul(matmul_in)
    eps = m["rms_norm_eps"]
    b, t = tokens.shape
    kw = dict(matmul_in=matmul_in, fault=fault)
    per, periods, _ = _layout(m)

    def layer(kind):
        @jax.checkpoint
        def run(x, lp):
            x = attention_block(x, lp, m, kind, **kw)
            y = _norm(x, lp["mlp_norm"], eps)
            out, selected = moe_mlp(y.reshape(b * t, -1), lp, m, **kw)
            return x + out.reshape(x.shape), selected
        return run

    at = lambda tree, *i: {k: v[i] for k, v in tree.items()}   # noqa: E731
    x = params["embed"][tokens]
    selections = []
    for i, kind in enumerate(_kinds(m)):
        period, place = divmod(i, per + 1)
        if period >= periods:
            lp = at(params["tail"], place)
        elif place < per:
            lp = at(params["window"], period, place)
        else:
            lp = at(params["full"], period)
        if fault == "full_first":
            kind = "full" if place == 0 else "window"
        x, selected = layer(kind)(x, lp)
        selections.append(selected)
    logits = mm(_norm(x, params["final_norm"], eps), params["lm_head"])
    return logits, jnp.stack(selections)


def mellum_loss(params, tokens, m: dict, *, matmul_in=None, keep=None,
                fault=None):
    """(next-token cross-entropy of the plain forward pass, the layers'
    selections). ``matmul_in`` rounds every matmul operand (the
    low-precision control); ``keep`` = number of leading positions whose
    loss counts (the half-batch fault)."""
    import jax
    import jax.numpy as jnp

    logits, selections = mellum_logits(params, tokens, m,
                                       matmul_in=matmul_in, fault=fault)
    logits, targets = logits[:, :-1], tokens[:, 1:]
    nll = (jax.nn.logsumexp(logits, axis=-1) -
           jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0])
    if keep is not None:
        nll = nll[:, :keep]
    return jnp.mean(nll), selections


def train_reference(seed: int, m: dict, o: dict, tokens, steps: int, *,
                    matmul_in=None, keep=None, fault=None) -> dict:
    """Follows the first ``steps`` steps from the seed, as
    ``reference_swa.train_reference`` does: each step's loss, the per-leaf
    norm of the first gradient, the per-leaf norm of the parameters' change
    after the last step, and the first step's selections. Gradients and
    updates are separate donated programs, and between updates the moments
    live on the host, so that the gradient program has the device to
    itself."""
    import jax
    import jax.numpy as jnp

    key = reference.seed_key(seed)
    params = jax.jit(lambda k: mellum_init(k, m))(key)
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
    mu = nu = None
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t: mellum_loss(p, t, m, matmul_in=matmul_in, keep=keep,
                                 fault=fault), has_aux=True))
    update = jax.jit(
        lambda p, a, b, g, c: reference.adamw_update(p, a, b, g, c, o),
        donate_argnums=(0, 1, 2, 3))
    norms = jax.jit(reference.leaf_norms)
    delta = jax.jit(lambda p, k: reference.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, mellum_init(k, m))))
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
