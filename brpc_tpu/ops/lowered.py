"""Which implementation a traced choice came to, counted where it is settled.

``lax.platform_dependent`` traces every branch, so nothing that runs while a
function is traced can say which branch the program will hold: that is
decided when the program is lowered for a platform, and only the branch kept
is lowered. ``count_lowering(x, name)`` is the identity on ``x`` and emits no
operation; each time its equation is lowered (or, outside ``jit``, run) it
adds one to the ``obs`` counter ``name``. Tangents and cotangents pass it by,
so a backward pass counts nothing.
"""

from __future__ import annotations

from jax.extend import core as jex_core
from jax.interpreters import ad, batching, mlir

from brpc_tpu import obs

_count_p = jex_core.Primitive("count_lowering")


def _bump(name: str) -> None:
    if obs.enabled():
        obs.counter(name).add(1)


def _impl(x, *, name):
    _bump(name)
    return x


def _lowering(ctx, x, *, name):
    _bump(name)
    return [x]


_count_p.def_impl(_impl)
_count_p.def_abstract_eval(lambda x, *, name: x)
mlir.register_lowering(_count_p, _lowering)
ad.primitive_jvps[_count_p] = lambda primals, tangents, *, name: (
    _count_p.bind(*primals, name=name), tangents[0])
batching.primitive_batchers[_count_p] = lambda args, dims, *, name: (
    _count_p.bind(*args, name=name), dims[0])


def count_lowering(x, name: str):
    return _count_p.bind(x, name=name)
