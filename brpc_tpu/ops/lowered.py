"""What the kernels of ``ops/`` share: the room a kernel may take in VMEM, its
compiler parameters, and the choice between a kernel and its plain form,
counted where it is settled.

``lax.platform_dependent`` traces every branch, so nothing that runs while a
function is traced can say which branch the program will hold: that is
decided when the program is lowered for a platform, and only the branch kept
is lowered. ``count_lowering(x, name)`` is the identity on ``x`` and emits no
operation; each time its equation is lowered (or, outside ``jit``, run) it
adds one to the ``obs`` counter ``name``. Tangents and cotangents pass it by,
so a backward pass counts nothing. ``choose`` is that choice for a kernel of
this package: by the operands while tracing, by the platform when lowered.
"""

from __future__ import annotations

from jax import lax
from jax.experimental.pallas import tpu as pltpu
from jax.extend import core as jex_core
from jax.interpreters import ad, batching, mlir

from brpc_tpu import obs

VMEM_LIMIT = 64 << 20    # of a v5e core's 128 MiB; Mosaic's default is 16

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


def compiler_params(semantics: tuple):
    """Mosaic's parameters for a kernel whose grid axes run as ``semantics``
    says, each ``"parallel"`` or ``"arbitrary"`` (in order: it carries
    something from one step to the next), with ``VMEM_LIMIT``."""
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def choose(kernel, plain, taken: bool, counter: str, interpret, *operands):
    """``kernel(*operands, interpret=)`` or ``plain(*operands)``: by the
    operands while tracing (``taken``), by the platform when lowered, where
    a program that keeps the kernel adds one to ``counter``. ``interpret``
    True / False forces the kernel through the Pallas interpreter or the
    compiler (tests)."""
    if interpret is not None:
        return kernel(*operands, interpret=interpret)
    if not taken:
        return plain(*operands)
    return lax.platform_dependent(
        *operands,
        tpu=lambda first, *rest: kernel(count_lowering(first, counter),
                                        *rest, interpret=False),
        default=plain)
