"""Plain references: what the timed path has to have produced.

Nothing here imports the program or takes anything the program made. The
embedding shard's reference is numpy float32 over the seeded table; the
train step's is the published Llama-family equations (Mistral-7B: RMSNorm,
RoPE, grouped-query causal attention, SwiGLU, untied head) and AdamW in
``jax.numpy`` float32 at ``highest`` matmul precision, with no scan, cache
or kernel.
"""

from __future__ import annotations

import numpy as np

EPS32 = float(np.finfo(np.float32).eps)


# ---------------------------------------------------------------------------
# embedding shard
# ---------------------------------------------------------------------------

def segment_sums(ids: np.ndarray, *row_blocks):
    """Per distinct id the float32 sum of its rows in each block
    (duplicates in a batch add up, as a scatter's must). Returns (distinct
    ids, counts, sums of each block)."""
    order = np.argsort(ids, kind="stable")
    sorted_ids = ids[order]
    starts = np.flatnonzero(np.r_[True, sorted_ids[1:] != sorted_ids[:-1]])
    counts = np.diff(np.r_[starts, ids.size])
    sums = [np.add.reduceat(rows[order], starts, axis=0) for rows in row_blocks]
    return (sorted_ids[starts], counts, *sums)


class RowLedger:
    """The reference's copy of the rows that a run checks: their values
    after every acknowledged update, and the bound within which any order
    of the same float32 sums must agree.

    An updated row is ``row - lr*(g1 + ... + gm)``; the order of that sum is
    the only freedom the device has. Any order of m+1 float32 terms is
    within ``m * eps * sum|terms|`` of exact, so the ledger keeps per
    element the sum of magnitudes and per row the count of terms. A gap is
    reported in units of ``(m+1) * eps * sum|terms|``: two sound orders
    differ by at most 2 of them, a never-updated row by 0."""

    def __init__(self, table: np.ndarray, tracked_ids: np.ndarray):
        self.ids = np.unique(tracked_ids)
        self.rows = table[self.ids].copy()
        self.sum_abs = np.abs(self.rows)
        self.terms = np.zeros(self.ids.size, np.int64)

    def index_of(self, ids: np.ndarray) -> np.ndarray:
        idx = np.searchsorted(self.ids, ids)
        if (idx >= self.ids.size).any() or (self.ids[idx] != ids).any():
            raise KeyError("an id that the ledger does not track")
        return idx

    def contribution(self, ids: np.ndarray, block: np.ndarray,
                     scale: float, lr: float):
        """What one acknowledged apply of the gradients ``block * scale``
        subtracts from tracked rows: (ledger indices, lr * summed
        gradients, sums of magnitudes, term counts). Reads the ledger's
        ids only, so contributions can be worked out side by side."""
        pos = np.flatnonzero(np.isin(ids, self.ids))
        dim = self.rows.shape[1]
        if not pos.size:
            return (np.zeros(0, np.int64), np.zeros((0, dim), np.float32),
                    np.zeros((0, dim), np.float32), np.zeros(0, np.int64))
        step = np.float32(lr) * (block[pos] * np.float32(scale))
        uniq, counts, sums, abs_sums = segment_sums(ids[pos], step,
                                                    np.abs(step))
        return self.index_of(uniq), sums, abs_sums, counts

    def fold(self, contribution) -> None:
        idx, sums, abs_sums, counts = contribution
        self.rows[idx] -= sums
        self.sum_abs[idx] += abs_sums
        self.terms[idx] += counts

    def apply(self, ids: np.ndarray, grads: np.ndarray, lr: float) -> None:
        self.fold(self.contribution(ids, grads, 1.0, lr))

    def bound(self, idx: np.ndarray) -> np.ndarray:
        return ((self.terms[idx, None] + 1) * EPS32) * self.sum_abs[idx]

    def gap(self, ids: np.ndarray, got: np.ndarray) -> float:
        """The widest gap of ``got`` from the ledger's rows of ``ids``, in
        units of the reduction-order bound."""
        idx = self.index_of(ids)
        err = np.abs(got - self.rows[idx])
        return float(np.max(err / np.maximum(self.bound(idx), 1e-45)))


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """``x`` as a bfloat16 computation would return it (round to nearest
    even on the top 16 bits), widened back to float32."""
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


# ---------------------------------------------------------------------------
# train step (Mistral-7B / Llama family), AdamW
# ---------------------------------------------------------------------------

def seed_key(seed: int, stream: int = 0):
    """The JAX key of one stream of a seed (any whole number up to 2**62).
    Made outside any jitted function and passed in, so that the programs do
    not depend on the seed and every seed finds them in the cache."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2 ** 31)),
                             int(seed) // (2 ** 31))
    return jax.random.fold_in(key, stream)


def llama_init(key, m: dict):
    """The weights of a run, from the seed's key, in float32, as one traced
    function (the caller jits it): normal(0, fan_in^-0.5) matrices, unit
    norms. The tree is the one the program's step takes: per-layer tensors
    stacked on a leading axis."""
    import jax
    import jax.numpy as jnp

    h, L, v = m["hidden_size"], m["num_hidden_layers"], m["vocab_size"]
    inter = m["intermediate_size"]
    hq = m["num_attention_heads"] * m["head_dim"]
    hkv = m["num_key_value_heads"] * m["head_dim"]
    k = jax.random.split(key, 9)

    def dense(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5

    return {
        "embed": dense(k[0], (v, h), 1.0),
        "layers": {
            "wq": dense(k[1], (L, h, hq), h),
            "wk": dense(k[2], (L, h, hkv), h),
            "wv": dense(k[3], (L, h, hkv), h),
            "wo": dense(k[4], (L, hq, h), hq),
            "w_gate": dense(k[5], (L, h, inter), h),
            "w_up": dense(k[6], (L, h, inter), h),
            "w_down": dense(k[7], (L, inter, h), inter),
            "attn_norm": jnp.ones((L, h), jnp.float32),
            "mlp_norm": jnp.ones((L, h), jnp.float32),
        },
        "final_norm": jnp.ones((h,), jnp.float32),
        "lm_head": dense(k[8], (h, v), h),
    }


def token_batches(seed: int, n: int, batch: int, seq: int, vocab: int):
    """``n`` batches of token ids, every row different, from the seed."""
    import jax

    return jax.jit(lambda k: jax.random.randint(
        k, (n, batch, seq), 0, vocab))(seed_key(seed, 7))


def fp8_operand(x):
    """A matmul operand as a plain fp8 (e4m3) computation sees it: the
    values rounded on the way in and, since the rounding is part of the
    traced function, the cotangents rounded on the way back."""
    import jax.numpy as jnp

    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def llama_loss(params, tokens, m: dict, *, matmul_in=None, keep=None):
    """Next-token cross-entropy of the plain forward pass, float32.
    ``matmul_in`` rounds every matmul operand (the low-precision control);
    ``keep`` = number of leading positions whose loss counts (the
    half-batch fault); both None in the reference."""
    import jax
    import jax.numpy as jnp

    q8 = matmul_in or (lambda x: x)

    def mm(a, b):
        return jnp.matmul(q8(a), q8(b), precision="highest")

    def norm(x, w):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + m["rms_norm_eps"]) * w

    def rope(x):                                     # [B,T,H,D]
        d_half = x.shape[-1] // 2
        freqs = m["rope_theta"] ** (
            -jnp.arange(0, d_half, dtype=jnp.float32) / d_half)
        ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        x1, x2 = x[..., :d_half], x[..., d_half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    b, t = tokens.shape
    nh, nkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    mask = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def attend(qkv):
        """One KV head with its group of query heads: [b,t,g,d], [b,t,d],
        [b,t,d]. Head groups are taken one after another, and recomputed
        in the backward pass, so that float32 scores of all heads are
        never live at once: the mathematics is that of all heads."""
        q, k, v = qkv
        s = jnp.einsum("btgd,bsd->bgts", q8(q), q8(k),
                       precision="highest") * d ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bgts,bsd->btgd", q8(p), q8(v), precision="highest")

    @jax.checkpoint
    def layer(x, lp):
        y = norm(x, lp["attn_norm"])
        q = rope(mm(y, lp["wq"]).reshape(b, t, nh, d))
        k = rope(mm(y, lp["wk"]).reshape(b, t, nkv, d))
        v = mm(y, lp["wv"]).reshape(b, t, nkv, d)
        q = jnp.moveaxis(q.reshape(b, t, nkv, nh // nkv, d), 2, 0)
        o = jax.lax.map(attend, (q, jnp.moveaxis(k, 2, 0),
                                 jnp.moveaxis(v, 2, 0)))
        o = jnp.moveaxis(o, 0, 2).reshape(b, t, nh * d)
        x = x + mm(o, lp["wo"])
        y = norm(x, lp["mlp_norm"])
        return x + mm(jax.nn.silu(mm(y, lp["w_gate"])) * mm(y, lp["w_up"]),
                      lp["w_down"])

    x = params["embed"][tokens]
    for i in range(m["num_hidden_layers"]):
        x = layer(x, {k: v[i] for k, v in params["layers"].items()})
    logits = mm(norm(x, params["final_norm"]), params["lm_head"])[:, :-1]
    targets = tokens[:, 1:]
    nll = (jax.nn.logsumexp(logits, axis=-1) -
           jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0])
    if keep is not None:
        nll = nll[:, :keep]
    return jnp.mean(nll)


def adamw_update(params, mu, nu, grads, count, o: dict):
    """One AdamW step as published (decoupled weight decay, bias-corrected
    moments); ``count`` is the number of the step, from 1."""
    import jax
    import jax.numpy as jnp

    b1, b2 = o["b1"], o["b2"]
    c = jnp.asarray(count, jnp.float32)

    def leaf(p, m_, v_, g):
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        m_hat = m_ / (1 - b1 ** c)
        v_hat = v_ / (1 - b2 ** c)
        step = m_hat / (jnp.sqrt(v_hat) + o["eps"]) + o["weight_decay"] * p
        return p - o["learning_rate"] * step, m_, v_

    out = jax.tree_util.tree_map(leaf, params, mu, nu, grads)
    pick = lambda i: jax.tree_util.tree_map(      # noqa: E731
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def leaf_norms(tree):
    """The Euclidean norm of every leaf, as one flat dict of floats-to-be
    (traced; the caller jits)."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): jnp.sqrt(jnp.sum(
        jnp.square(leaf.astype(jnp.float32)))) for path, leaf in flat}


def train_reference(seed: int, m: dict, o: dict, tokens, steps: int, *,
                    matmul_in=None, keep=None) -> dict:
    """Follows the first ``steps`` steps from the seed. Returns each step's
    loss, the per-leaf norm of the first gradient and the per-leaf norm of
    the parameters' change after the last step. Gradients and updates are
    separate donated programs so that one copy of the state is live."""
    import jax
    import jax.numpy as jnp

    key = seed_key(seed)
    params = jax.jit(lambda k: llama_init(k, m))(key)
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
    mu = nu = None
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t: llama_loss(p, t, m, matmul_in=matmul_in, keep=keep)))
    update = jax.jit(lambda p, a, b, g, c: adamw_update(p, a, b, g, c, o),
                     donate_argnums=(0, 1, 2, 3))
    norms = jax.jit(leaf_norms)
    delta = jax.jit(lambda p, k: leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, llama_init(k, m))))
    losses, grad_norms = [], None
    for i in range(steps):
        loss, grads = grad_fn(params, tokens[i])
        losses.append(float(loss))
        if i == 0:
            grad_norms = {k: float(v) for k, v in norms(grads).items()}
        if mu is None:      # after the first gradients: they need the room
            mu, nu = zeros(params), zeros(params)
        params, mu, nu = update(params, mu, nu, grads, i + 1)
    delta_norms = {k: float(v) for k, v in delta(params, key).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms}


def worst_leaf_gap(got: dict, want: dict) -> float:
    """The widest gap between a leaf's norm here and in the reference,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    median = float(np.median(list(want.values())))
    return max(abs(got[k] - want[k]) / max(want[k], median) for k in want)


def moving_leaves(grad_norms: dict) -> list:
    """Leaves whose first gradient is not nought to rounding: at least a
    thousandth of the median leaf's. The others move under Adam by
    round-off alone and are left out of the change's comparison."""
    median = float(np.median(list(grad_norms.values())))
    return [k for k, v in grad_norms.items() if v >= 1e-3 * median]
