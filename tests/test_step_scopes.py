"""Every array operation that a line of a model's ``loss_fn`` /
``make_train_step`` writes sits under a ``jax.named_scope`` of the program:
the jaxpr of each model's train step is walked, sub-jaxprs included, and an
equation of a primitive that does work on the device must carry a scope in
its name stack joined to its enclosing equations' -- by the rule the
benchmark's table of device time reads (``trace_reduce.scope_of``:
``jvp(a.b)`` and ``transpose(jvp(a.b))`` carry the forward line's scope).
What a transform writes (``add_any``, ``broadcast_in_dim``, a scan's own
equation, ...) is not the program's to name and is not looked at.
"""

import os
import sys

import jax
import jax.numpy as jnp
import optax
import pytest
from jax._src import core, source_info_util

from brpc_tpu.models import deepseek, hybrid, llama, looped, mamba, windowed

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))

import trace_reduce  # noqa: E402

# Primitives that stand for device work a line of the program asked for.
WORK = {"dot_general", "pallas_call", "conv_general_dilated", "gather",
        "scatter-add", "sort", "top_k", "cumsum", "reduce_sum", "reduce_max",
        "exp", "logistic", "rsqrt"}

_SHARED = {"embed", "weights.cast", "opt.update"}
_MOE = {"moe.router", "moe.sort", "moe.experts", "moe.combine", "moe.shared"}
_CHUNKED = {"loss.chunk", "loss.chunk/loss.logits", "loss.chunk/loss.nll"}
# model -> (its tiny config, the scopes its step must write; on the CPU at
# these sizes attention is the dense form, so ``attn.layout`` and the
# kernels' scopes are tests/test_ops.py's, compiled for a v5e)
MODELS = {
    "llama": (llama, llama.LlamaConfig.tiny(), _SHARED | {
        "llama.qkv", "llama.attn_out", "llama.mlp", "llama.head_loss",
        "attn.dense"}),
    "deepseek": (deepseek, deepseek.DeepseekConfig.tiny(),
                 _SHARED | _MOE | _CHUNKED | {
                     "mla.norm", "mla.q_proj", "mla.kv_down", "mla.kv_up",
                     "mla.rope", "mla.out_proj", "attn.dense",
                     "dsv3.dense_mlp", "dsv3.glue"}),
    "looped": (looped, looped.LoopedConfig.tiny(), _SHARED | {
        "loop.layer.attn", "loop.layer.attn/attn.dense", "loop.layer.mlp",
        "loop.pass_norm", "loop.exit_gate", "loop.head",
        "loop.head/loss.logits", "loop.head/loss.nll",
        "loop.head/loop.exit_loss"}),
    "hybrid": (hybrid, hybrid.HybridConfig.tiny(),
               _SHARED | _MOE | _CHUNKED | {
                   "hybrid.glue", "gdn.in_proj", "gdn.conv", "gdn.rule",
                   "gdn.out", "gattn.qkv", "gattn.out", "attn.dense"}),
    "windowed": (windowed, windowed.WindowedConfig.tiny(),
                 _SHARED | _MOE | _CHUNKED | {
                     "windowed.glue", "dense.mlp", "swa.qkv", "swa.rope",
                     "swa.attn/attn.dense", "swa.out", "full.qkv",
                     "full.rope", "full.attn/attn.dense", "full.out"}),
    # the same file laid out from Mellum2's tables: q/k norms, no gate, no
    # dense layer, no shared expert
    "windowed_mellum": (
        windowed, windowed.WindowedConfig.tiny_mellum2(),
        _SHARED | (_MOE - {"moe.shared"}) | _CHUNKED | {
            "windowed.glue", "swa.qkv", "swa.qknorm", "swa.rope",
            "swa.attn/attn.dense", "swa.out", "full.qkv", "full.qknorm",
            "full.rope", "full.attn/attn.dense", "full.out"}),
    # nine Mamba-2 layers to one attention layer, a dense MLP after each
    "mamba": (mamba, mamba.MambaConfig.tiny(), _SHARED | _CHUNKED | {
        "ssm.glue", "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.norm",
        "ssm.out", "gqa.qkv", "gqa.out", "attn.dense", "mlp"}),
}
# scopes a model's step must NOT write: what its configuration has not
ABSENT = {"windowed_mellum": {"moe.shared", "dense.mlp"},
          "mamba": _MOE,
          "windowed": {"swa.qknorm", "full.qknorm"}}


def _equations(jaxpr, stack=""):
    """(equation, its name stack joined to its enclosing equations')."""
    for eqn in jaxpr.eqns:
        here = "/".join(
            part for part in (stack, str(eqn.source_info.name_stack)) if part)
        yield eqn, here
        for sub in core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, here)


def step_scopes(step, *args):
    """(the scopes of the step's equations, the equations of ``WORK`` under
    none, each as "primitive at file:line (function)")."""
    seen, bare = set(), []
    for eqn, stack in _equations(jax.make_jaxpr(step)(*args).jaxpr):
        name = eqn.primitive.name
        scope, _ = trace_reduce.scope_of(f"jit(step)/{stack}/{name}")
        seen.add(scope)
        if name in WORK and scope == trace_reduce.NO_SCOPE:
            bare.append(f"{name} at "
                        f"{source_info_util.summarize(eqn.source_info)}")
    return seen, bare


@pytest.mark.parametrize("model", sorted(MODELS))
def test_every_line_of_the_train_step_is_under_a_scope(model):
    mod, cfg, want = MODELS[model]
    optimizer = optax.adamw(1e-3)
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    seen, bare = step_scopes(mod.make_train_step(cfg, optimizer), params,
                             optimizer.init(params),
                             jnp.zeros((2, 64), jnp.int32))
    assert not bare, "under no scope of the program:\n" + "\n".join(bare)
    assert want <= seen, sorted(want - seen)
    assert not ABSENT.get(model, set()) & seen


def test_the_walk_finds_a_line_left_bare():
    """The rule has teeth: a product outside every scope is named with its
    source line, one inside a differentiated scope is not."""
    def step(w, x):
        def loss(w):
            with jax.named_scope("named"):
                y = jnp.tanh(x @ w)
            return jnp.sum(y @ w.T)         # bare: a product and a sum
        return jax.grad(loss)(w)

    seen, bare = step_scopes(step, jnp.ones((4, 4)), jnp.ones((2, 4)))
    assert "named" in seen
    assert bare and all("test_step_scopes.py" in line for line in bare)
    assert {line.split(" at ")[0] for line in bare} == {"dot_general",
                                                        "reduce_sum"}
