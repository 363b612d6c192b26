"""Plain reference of the DeepSeek-V3-shaped train step (kanana-2-30b-a3b):
the published equations — multi-head latent attention, sigmoid-routed
experts with shared experts, one chip's share of the experts — and AdamW in
``jax.numpy`` float32 at ``highest`` matmul precision. No kernel, no
sorting, no grouped product: every held expert runs on every token and is
weighted by the router (zero where it was not selected).

Nothing here imports the program (``brpc_tpu.models``) or takes anything the
program made; ``reference.py``'s helpers (keys, tokens, AdamW, norms, the fp8
operand) are shared. ``m`` is the model's sizes under the names of the
published ``config.json``, with ``n_routed_experts`` the experts HELD
(``expert_offset`` on) and ``router_experts`` the published count, which
the router keeps.

Departures from the published model, the same as the program's: the
selection bias ``e_score_correction_bias`` is a seeded buffer held fixed
over the steps, no auxiliary loss, ``n_group`` = ``topk_group`` = 1.
"""

from __future__ import annotations

import reference


def dsv3_init(key, m: dict):
    """The weights of a run, from the seed's key, float32, one traced
    function: normal(0, fan_in^-0.5) matrices, unit norms, ``router_bias``
    normal(0, 0.01). The tree is the one the program's step takes: dense and
    expert layers stacked apart on a leading axis."""
    import jax
    import jax.numpy as jnp

    h, v = m["hidden_size"], m["vocab_size"]
    nh, rank = m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    n_dense = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - n_dense
    f, held = m["moe_intermediate_size"], m["n_routed_experts"]
    shared = m["n_shared_experts"] * f
    keys = iter(jax.random.split(key, 32))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) \
            * fan_in ** -0.5

    def attention(n):
        return {
            "wq": dense((n, h, nh * (nope + rope)), h),
            "wkv_a": dense((n, h, rank + rope), h),
            "wkv_b": dense((n, rank, nh * (nope + dv)), rank),
            "wo": dense((n, nh * dv, h), nh * dv),
            "kv_norm": jnp.ones((n, rank), jnp.float32),
            "attn_norm": jnp.ones((n, h), jnp.float32),
            "mlp_norm": jnp.ones((n, h), jnp.float32),
        }

    inter = m["intermediate_size"]
    return {
        "embed": dense((v, h), 1.0),
        "dense": {**attention(n_dense),
                  "w_gate": dense((n_dense, h, inter), h),
                  "w_up": dense((n_dense, h, inter), h),
                  "w_down": dense((n_dense, inter, h), inter)},
        "moe": {**attention(n_moe),
                "router": dense((n_moe, h, m["router_experts"]), h),
                "router_bias": 0.01 * jax.random.normal(
                    next(keys), (n_moe, m["router_experts"]), jnp.float32),
                "shared_gate": dense((n_moe, h, shared), h),
                "shared_up": dense((n_moe, h, shared), h),
                "shared_down": dense((n_moe, shared, h), shared),
                "w_gate": dense((n_moe, held, h, f), h),
                "w_up": dense((n_moe, held, h, f), h),
                "w_down": dense((n_moe, held, f, h), f)},
        "final_norm": jnp.ones((h,), jnp.float32),
        "lm_head": dense((h, v), h),
    }


def _matmul(matmul_in):
    import jax.numpy as jnp

    q8 = matmul_in or (lambda x: x)
    return q8, lambda a, b: jnp.matmul(q8(a), q8(b), precision="highest")


def _norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def moe_mlp(y, lp, m: dict, *, matmul_in=None, fault=None):
    """The expert layer's MLP on normed tokens y [N, H]: the share that
    experts ``expert_offset`` .. + ``n_routed_experts`` give, plus the
    shared experts. Returns (result, selected experts [N, k]).
    ``fault``: ``drop_sixth`` leaves out the last selected expert's
    contribution, ``no_scaling`` the routed scaling factor."""
    import jax
    import jax.numpy as jnp

    _, mm = _matmul(matmul_in)
    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(mm(y, lp["router"]))
    _, selected = jax.lax.top_k(s + lp["router_bias"], k)
    w = jnp.take_along_axis(s, selected, axis=1)
    w = w / jnp.sum(w, axis=1, keepdims=True)
    if fault != "no_scaling":
        w = w * m["routed_scaling_factor"]
    if fault == "drop_sixth":
        w = w.at[:, -1].set(0.0)

    def swiglu(gate, up, down):
        return mm(jax.nn.silu(mm(y, gate)) * mm(y, up), down)

    @jax.checkpoint
    def one_expert(out, args):
        e, gate, up, down = args
        weight = jnp.sum(jnp.where(selected == e, w, 0.0), axis=1)
        return out + weight[:, None] * swiglu(gate, up, down), None

    out = swiglu(lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    experts = m.get("expert_offset", 0) + jnp.arange(m["n_routed_experts"])
    out, _ = jax.lax.scan(one_expert, out, (experts, lp["w_gate"],
                                            lp["w_up"], lp["w_down"]))
    return out, selected


def dsv3_loss(params, tokens, m: dict, *, matmul_in=None, keep=None,
              fault=None):
    """(next-token cross-entropy of the plain forward pass in float32, the
    experts each expert layer selected [L, B*T, k]). ``matmul_in`` rounds
    every matmul operand (the low-precision control); ``keep`` = number of
    leading positions whose loss counts (the half-batch fault); ``fault``
    as ``moe_mlp``'s."""
    import jax
    import jax.numpy as jnp

    q8, mm = _matmul(matmul_in)
    eps = m["rms_norm_eps"]
    b, t = tokens.shape
    nh, rank = m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    mask = jnp.tril(jnp.ones((t, t), bool))

    def rotate(x):                       # [b,t,H,rope], pairs (2i, 2i+1)
        freqs = m["rope_theta"] ** (
            -jnp.arange(0, rope // 2, dtype=jnp.float32) / (rope // 2))
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                         axis=-1).reshape(x.shape)

    @jax.checkpoint
    def attend(qkv):
        """One head: [b,t,192], [b,t,192], [b,t,128]. Heads are taken one
        after another and recomputed in the backward pass, so that one
        head's float32 scores are live at a time."""
        q, k, v = qkv
        s = jnp.einsum("btd,bsd->bts", q8(q), q8(k),
                       precision="highest") * (nope + rope) ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", q8(p), q8(v), precision="highest")

    def attention(x, lp):
        y = _norm(x, lp["attn_norm"], eps)
        q = mm(y, lp["wq"]).reshape(b, t, nh, nope + rope)
        ckv = mm(y, lp["wkv_a"])
        latent = _norm(ckv[..., :rank], lp["kv_norm"], eps)
        kv = mm(latent, lp["wkv_b"]).reshape(b, t, nh, nope + dv)
        k_rope = jnp.broadcast_to(rotate(ckv[:, :, None, rank:]),
                                  (b, t, nh, rope))
        q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:])], axis=-1)
        k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
        o = jax.lax.map(attend, tuple(
            jnp.moveaxis(a, 2, 0) for a in (q, k, kv[..., nope:])))
        return x + mm(jnp.moveaxis(o, 0, 2).reshape(b, t, nh * dv), lp["wo"])

    @jax.checkpoint
    def dense_layer(x, lp):
        x = attention(x, lp)
        y = _norm(x, lp["mlp_norm"], eps)
        return x + mm(jax.nn.silu(mm(y, lp["w_gate"])) * mm(y, lp["w_up"]),
                      lp["w_down"])

    @jax.checkpoint
    def moe_layer(x, lp):
        x = attention(x, lp)
        y = _norm(x, lp["mlp_norm"], eps).reshape(b * t, -1)
        out, selected = moe_mlp(y, lp, m, matmul_in=matmul_in, fault=fault)
        return x + out.reshape(x.shape), selected

    def layers(tree):
        n = tree["wq"].shape[0]
        return [{k: v[i] for k, v in tree.items()} for i in range(n)]

    x = params["embed"][tokens]
    for lp in layers(params["dense"]):
        x = dense_layer(x, lp)
    selections = []
    for lp in layers(params["moe"]):
        x, selected = moe_layer(x, lp)
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
    ``reference.train_reference`` does: each step's loss, the per-leaf norm
    of the first gradient, the per-leaf norm of the parameters' change after
    the last step, and the first step's selections. ``router_bias`` is put
    back after every update: it is a buffer. Gradients and updates are
    separate donated programs, and between updates the moments live on the
    host, so that the gradient program has the device to itself."""
    import jax
    import jax.numpy as jnp

    key = reference.seed_key(seed)
    params = jax.jit(lambda k: dsv3_init(k, m))(key)
    bias = jnp.copy(params["moe"]["router_bias"])
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
    mu = nu = None
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t: dsv3_loss(p, t, m, matmul_in=matmul_in, keep=keep,
                               fault=fault), has_aux=True))
    update = jax.jit(
        lambda p, a, b, g, c: reference.adamw_update(p, a, b, g, c, o),
        donate_argnums=(0, 1, 2, 3))
    norms = jax.jit(reference.leaf_norms)
    delta = jax.jit(lambda p, k: reference.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, dsv3_init(k, m))))
    losses, grad_norms, selected = [], None, None
    for i in range(steps):
        (loss, chosen), grads = grad_fn(params, tokens[i])
        losses.append(float(loss))
        if i == 0:
            grad_norms = {k: float(v) for k, v in norms(grads).items()}
            selected = chosen
        # The moments wait on the host while the gradient program runs: its
        # temporaries (8.0 GiB at the cell's size, AOT) do not fit beside
        # parameters, gradients and both moments.
        moments = (zeros(params), zeros(params)) if mu is None else \
            jax.device_put((mu, nu))
        params, *moments = update(params, *moments, grads, i + 1)
        params["moe"]["router_bias"] = jnp.copy(bias)   # the update donates
        if i + 1 < steps:
            mu, nu = jax.device_get(moments)
        del moments, grads
    delta_norms = {k: float(v) for k, v in delta(params, key).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms, "selected": selected}
