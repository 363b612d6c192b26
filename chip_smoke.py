#!/usr/bin/env python3
"""The quickest proof that the fabric still starts on the chip.

    python chip_smoke.py              one TPU chip: phases kernel, train, ps
                                      (+ the multichip phases when the host
                                      reports four chips)
    python chip_smoke.py --chips 4    the same, four chips required
    python chip_smoke.py --cpu-dry-run [--chips 4]
                                      tiny sizes on the CPU: fake PJRT
                                      plug-in, interpret-mode kernel

It drives the two halves of the main path once, through the entry points a
user calls, at Llama-3-8B widths with seeded random weights:

  kernel   the Pallas flash-attention kernels, forward and backward,
           compiled (not interpreted), at Llama-3-8B head geometry: output,
           dQ, dK and dV against float32 ``llama.dense_attention``, and one
           forward + backward of kernels and dense form timed side by side.
  train    three ``llama.make_train_step`` steps (AdamW, donated state) on
           one seeded batch: loss finite and falling.
  ps       ``DevicePsShardServer`` holding Llama-3-8B's 128,256 x 4,096
           embedding in HBM through the native PJRT client, served over
           loopback RPC to a ``RemoteEmbedding``: Lookups and
           ``apply_gradients`` of zipf ids against a float32 numpy reference.
  multichip_jax / multichip_native (four chips)
           the sharded-vs-single-device asserts of
           ``__graft_entry__._dryrun_impl`` on the real devices, the tp=2 x
           sp=2 ring-attention step at Llama-3-8B widths, an all-reduce over
           four chips; natively a 4-replica all-reduce executable and four
           embedding shards, shard i on device i.

One process owns a chip at a time, so this parent imports neither jax nor
the native core: every phase is a child, run in turn, and prints one JSON
line naming the platform, device kind and device count it ran on, with
set-up seconds (build, client start, stage-up, each compilation) apart from
run seconds. It reports no rates. The run stops at the first phase that
fails and exits non-zero; a phase that finds anything but a TPU fails. Only
``--cpu-dry-run`` runs on the CPU, and it says so.

After the phases' lines come ``{"phases": [...], "claim": null}`` and, last,
the verdict with exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
A run that fails after a phase named the device ends ``"ok": false``; one
that finds no TPU prints no verdict at all.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1100          # the whole run; the contract allows 1,200
LIBTPU_LOCKFILE = "/tmp/libtpu_lockfile"
SEED = 20260926


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Sizes:
    """What each phase runs at. ``REAL`` is Llama-3-8B's published widths
    with the depth, batch and (for one chip's train step) vocabulary cut
    until AdamW state fits 16 GB; ``DRY`` only has to finish on a CPU."""
    tiny_model: bool           # LlamaConfig.tiny() instead of llama3_8b()
    train_layers: int
    train_vocab: int
    train_tokens: tuple        # (batch, seq) on one chip
    ring_vocab: int
    ring_tokens: tuple         # (batch, seq) over tp=2 x sp=2
    kernel_shape: tuple        # (B, T, Hq, Hkv, D), bf16
    ps_vocab: int
    ps_dim: int
    ps_ids: int                # ids per Lookup / apply_gradients


REAL = Sizes(tiny_model=False, train_layers=2, train_vocab=32000,
             train_tokens=(1, 2048), ring_vocab=128256,
             ring_tokens=(2, 2048), kernel_shape=(1, 2048, 32, 8, 128),
             ps_vocab=128256, ps_dim=4096, ps_ids=2048)
DRY = Sizes(tiny_model=True, train_layers=2, train_vocab=512,
            train_tokens=(2, 64), ring_vocab=512, ring_tokens=(2, 64),
            kernel_shape=(1, 128, 4, 2, 32), ps_vocab=512, ps_dim=16,
            ps_ids=64)


# ---------------------------------------------------------------------------
# phases (each runs in its own child process)
# ---------------------------------------------------------------------------

class Timer:
    """Named wall-clock sections; ``seconds`` keeps them apart."""

    def __init__(self):
        self.seconds = {}

    @contextlib.contextmanager
    def __call__(self, name):
        t0 = time.monotonic()
        yield
        self.seconds[name] = round(time.monotonic() - t0, 3)


def _jax_devices(dry: bool):
    """The devices JAX reports, refused unless they are what this run is
    for: TPUs, or — only under --cpu-dry-run — the CPU."""
    import jax

    from brpc_tpu import compile_cache

    devices = jax.devices()
    want = "cpu" if dry else "tpu"
    if devices[0].platform != want:
        raise SystemExit(
            f"chip_smoke: JAX reports platform {devices[0].platform!r}, "
            f"this run needs {want!r}")
    cache_dir = compile_cache.enable()
    return devices, {"platform": devices[0].platform,
                     "device_kind": devices[0].device_kind,
                     "device_count": len(devices),
                     "compile_cache": cache_dir}


def _llama_config(sizes: Sizes, vocab: int):
    from brpc_tpu.models import llama

    base = (llama.LlamaConfig.tiny() if sizes.tiny_model
            else llama.LlamaConfig.llama3_8b())
    cfg = dataclasses.replace(base, n_layers=sizes.train_layers,
                              vocab_size=vocab)
    cut = {k: {"published": getattr(base, k), "here": getattr(cfg, k)}
           for k in ("n_layers", "vocab_size")
           if getattr(base, k) != getattr(cfg, k)}
    return cfg, {"model": "tiny" if sizes.tiny_model else "llama3_8b",
                 "hidden": cfg.hidden, "heads": [cfg.n_heads, cfg.n_kv_heads,
                                                 cfg.head_dim],
                 "intermediate": cfg.intermediate, "cut": cut}


def _train_steps(cfg, mesh, tokens_spec, tokens_shape, attn_fn, n_steps,
                 clock: Timer, prefix: str = ""):
    """Seeded init, then ``n_steps`` donated AdamW steps on one batch
    through the normal entry points (make_mesh / shard_params /
    make_train_step). Returns the losses."""
    import jax
    import optax

    from brpc_tpu.models import llama
    from brpc_tpu.parallel import shard_batch, shard_params

    # Adam's first steps move every weight by the learning rate whatever
    # the gradient: at hidden 4,096, 1e-3 took the loss from 10.9 to 0.04
    # in one step and back up to 0.8 in the next (seen on the chip).
    optimizer = optax.adamw(1e-4)
    with clock(prefix + "init_s"):
        params = shard_params(
            llama.init_params(jax.random.PRNGKey(SEED), cfg),
            llama.param_specs(cfg), mesh)
        opt_state = optimizer.init(params)
        tokens = shard_batch(
            jax.random.randint(jax.random.PRNGKey(SEED + 1), tokens_shape,
                               0, cfg.vocab_size), tokens_spec, mesh)
        jax.block_until_ready((params, opt_state, tokens))
    step = jax.jit(llama.make_train_step(cfg, optimizer, attn_fn),
                   donate_argnums=(0, 1))
    losses = []
    with mesh:
        with clock(prefix + "compile_s"):
            compiled = step.lower(params, opt_state, tokens).compile()
        with clock(prefix + "run_s"):
            for _ in range(n_steps):
                params, opt_state, loss = compiled(params, opt_state, tokens)
                losses.append(float(loss))
    return losses


def _check_losses(losses):
    import math

    if not all(math.isfinite(x) for x in losses):
        raise SystemExit(f"chip_smoke: loss not finite: {losses}")
    if any(b >= a for a, b in zip(losses, losses[1:])):
        raise SystemExit(
            f"chip_smoke: loss did not fall on a repeated batch: {losses}")


def phase_train(sizes: Sizes, dry: bool) -> dict:
    from brpc_tpu.models import llama
    from brpc_tpu.parallel import make_mesh

    clock = Timer()
    devices, out = _jax_devices(dry)
    cfg, out["config"] = _llama_config(sizes, sizes.train_vocab)
    out["tokens"] = list(sizes.train_tokens)
    losses = _train_steps(cfg, make_mesh({}, devices=devices[:1]),
                          llama.batch_specs(), sizes.train_tokens, None, 3,
                          clock)
    _check_losses(losses)
    out["losses"] = losses
    stats = devices[0].memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    out["bytes_limit"] = stats.get("bytes_limit")
    out["seconds"] = clock.seconds
    return out


def phase_kernel(sizes: Sizes, dry: bool) -> dict:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.models import llama
    from brpc_tpu.ops import flash_attention

    clock = Timer()
    _, out = _jax_devices(dry)
    b, t, hq, hkv, d = sizes.kernel_shape
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q = jax.random.normal(kq, (b, t, hq, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, t, hkv, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, t, hkv, d), jnp.bfloat16)
    cotangent = jax.random.normal(kw, (b, t, hq * d), jnp.float32)
    out["shape"] = list(sizes.kernel_shape)
    out["interpret"] = dry      # the Mosaic compiler exists only for TPUs

    def forward_backward(attn):
        """(o, dq, dk, dv) of one forward and one backward pass."""
        def run(q, k, v, cotangent):
            o, vjp = jax.vjp(attn, q, k, v)
            return (o, *vjp(cotangent.astype(o.dtype)))
        return jax.jit(run)

    args = (q, k, v, cotangent)
    dense_form = forward_backward(llama.dense_attention)
    with clock("compile_s"):
        lowered = forward_backward(functools.partial(
            flash_attention, interpret=dry)).lower(*args)
        if not dry and lowered.as_text().count("tpu_custom_call") < 2:
            raise SystemExit("chip_smoke: the lowered attention holds fewer "
                             "than two tpu_custom_calls: the forward and the "
                             "backward kernel did not both lower to Mosaic")
        kernel = lowered.compile()
        dense = dense_form.lower(*args).compile()
    with clock("run_s"):
        got = [np.asarray(x, np.float32) for x in kernel(*args)]
    with clock("reference_s"):
        with jax.default_matmul_precision("highest"):
            want = [np.asarray(x) for x in dense_form(
                *(x.astype(jnp.float32) for x in args))]
    # Forward + backward of the kernels and of the dense form in bf16, side
    # by side (not a rate: one op alone, outside any step).
    out["forward_backward_ms"] = {}
    reps = 1 if dry else 20
    for name, fn in (("kernel", kernel), ("dense", dense)):
        jax.block_until_ready(fn(*args))
        t0 = time.monotonic()
        for _ in range(reps):
            last = fn(*args)
        jax.block_until_ready(last)
        out["forward_backward_ms"][name] = round(
            (time.monotonic() - t0) / reps * 1e3, 3)
    # One rule for the four arrays: the largest error is within 2e-2 of the
    # array's scale. The output's scale is 1: O(1) averages of N(0,1) values
    # returned in bf16 (8 mantissa bits: 2^-9 = 0.002 relative), after bf16
    # operands on the scores and on the probabilities cost the same again.
    # A gradient's scale is its reference's largest element: dS is rounded
    # to bf16 as the probabilities are. 2e-2 passes that and fails a wrong
    # mask, scale, KV-head mapping or group sum (errors O(scale)).
    tol = 2e-2
    out["tolerance"] = tol
    out["max_err_over_scale"] = {}
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        if g.shape != w.shape or not np.isfinite(g).all():
            raise SystemExit(f"chip_smoke: kernel {name} {g.shape} not "
                             f"finite or not {w.shape}")
        scale = 1.0 if name == "o" else float(np.max(np.abs(w)))
        err = float(np.max(np.abs(g - w))) / scale
        out["max_err_over_scale"][name] = err
        if not err <= tol:
            raise SystemExit(f"chip_smoke: kernel {name} differs from "
                             f"float32 llama.dense_attention by {err} of "
                             f"its scale > {tol}")
    out["seconds"] = clock.seconds
    return out


def _native_client(dry: bool, clock: Timer):
    """The native PJRT client on the installed libtpu (default discovery),
    or — only under --cpu-dry-run — on the in-repo fake plug-in."""
    with clock("native_build_s"):
        from brpc_tpu import rpc

        # Builds and loads the core; a failure surfaces, with its reason,
        # from DeviceClient below.
        rpc.native_core_available()
        plugin = rpc.fake_pjrt_plugin_path() if dry else None
    with clock("client_init_s"):
        dev = rpc.DeviceClient(plugin)
    want = "brt_fake" if dry else "tpu"
    if dev.platform != want:
        raise SystemExit(f"chip_smoke: PJRT reports platform "
                         f"{dev.platform!r}, this run needs {want!r}")
    return rpc, dev, {"platform": "cpu" if dry else dev.platform,
                      "pjrt_platform": dev.platform,
                      "device_kind": dev.device_kind(0),
                      "device_count": dev.device_count}


def _shard_table(vocab, dim, shard_index, num_shards, seed):
    """What PsShardServer seeds shard ``shard_index`` with (ps_remote.py,
    PsShardServer.__init__), regenerated for the reference."""
    import numpy as np

    rng = np.random.default_rng(seed + shard_index)
    return (rng.standard_normal((vocab // num_shards, dim)) * 0.02
            ).astype(np.float32)


def _serve_and_check(emb, table, n_ids, lr, clock: Timer):
    """Lookups and apply_gradients of seeded zipf ids through ``emb``,
    against ``table`` (the float32 numpy reference, updated in place)."""
    import numpy as np

    from brpc_tpu import press

    vocab, dim = table.shape
    rng = np.random.default_rng(SEED)
    weights = press.zipf_weights(vocab, 1.1)
    checks = {"lookups": 0, "applies": 0, "max_duplicates": 0,
              "max_abs_err": 0.0}
    eps = np.finfo(np.float32).eps
    for round_no in range(2):
        ids = rng.choice(vocab, size=n_ids, p=weights).astype(np.int32)
        grads = rng.standard_normal((n_ids, dim)).astype(np.float32)
        uniq, inverse, counts = np.unique(ids, return_inverse=True,
                                          return_counts=True)
        # First round compiles the shard's gather and scatter for this
        # bucket: set-up. Second round is the run.
        with clock("first_round_s" if round_no == 0 else "run_s"):
            rows = emb.lookup(ids)
            emb.apply_gradients(ids, grads)
            after = emb.lookup(uniq)
        # A gather moves bits: the rows must be the reference's exactly.
        if not np.array_equal(rows, table[ids]):
            raise SystemExit("chip_smoke: Lookup rows differ from the "
                             "reference table")
        # The update is table - sum_j lr*g_j over an id's m duplicates; the
        # order of that sum is the only freedom the device has. Any order
        # of m+1 float32 terms is within m*eps*sum|terms| of exact, so two
        # orders differ by at most twice that.
        step = np.float32(lr) * grads
        sum_abs = np.abs(table[uniq])
        np.add.at(sum_abs, inverse, np.abs(step))
        np.subtract.at(table, ids, step)
        tol = 2.0 * (counts[:, None] + 1) * eps * sum_abs
        err = np.abs(after - table[uniq])
        if not (err <= tol).all():
            raise SystemExit(
                f"chip_smoke: applied rows differ from the reference by "
                f"{float(err.max())}, past the reduction-order bound")
        checks["lookups"] += 2
        checks["applies"] += 1
        checks["max_duplicates"] = max(checks["max_duplicates"],
                                       int(counts.max()))
        checks["max_abs_err"] = max(checks["max_abs_err"], float(err.max()))
    return checks


def _assert_no_leaked_handles(rpc):
    """Every native handle the phase made is destroyed (the C++ side's own
    counts). Stream teardown completes asynchronously: allow it a moment."""
    deadline = time.monotonic() + 5.0
    while True:
        live = {k: v for k, v in rpc.debug_handle_counts().items() if v}
        if not live or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    if live:
        raise SystemExit(f"chip_smoke: leaked native handles {live}")


def phase_ps(sizes: Sizes, dry: bool) -> dict:
    from brpc_tpu.ps_remote import DevicePsShardServer, RemoteEmbedding

    clock = Timer()
    rpc, dev, out = _native_client(dry, clock)
    vocab, dim, lr = sizes.ps_vocab, sizes.ps_dim, 0.1
    out["table"] = [vocab, dim]
    out["ids_per_call"] = sizes.ps_ids
    with clock("reference_table_s"):
        table = _shard_table(vocab, dim, 0, 1, SEED)
    # The same seeded init again inside the server, then the stage-up of
    # the whole table into HBM.
    with clock("server_init_and_stage_up_s"):
        srv = DevicePsShardServer(vocab, dim, 0, 1, lr=lr, seed=SEED,
                                  device_client=dev)
    emb = RemoteEmbedding([srv.address], vocab, dim, timeout_ms=120000)
    try:
        out["checks"] = _serve_and_check(emb, table, sizes.ps_ids, lr,
                                         clock)
        out["resident_device"] = srv.resident_device()
    finally:
        emb.close()
        srv.close()
        dev.close()
    _assert_no_leaked_handles(rpc)
    out["leaked_handles"] = 0
    out["seconds"] = clock.seconds
    return out


def phase_multichip_jax(sizes: Sizes, dry: bool) -> dict:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import __graft_entry__
    from brpc_tpu.parallel import (CollectiveChannel, make_mesh,
                                   ring_attention)

    clock = Timer()
    devices, out = _jax_devices(dry)
    if len(devices) < 4:
        raise SystemExit(f"chip_smoke: multichip needs four devices, JAX "
                         f"reports {len(devices)}")
    devices = devices[:4]
    # Its sharded-vs-single-device asserts are the check.
    with clock("dryrun_impl_s"):
        __graft_entry__._dryrun_impl(4)

    mesh = make_mesh({"tp": 2, "sp": 2}, devices=devices)
    cfg, out["config"] = _llama_config(sizes, sizes.ring_vocab)
    out["tokens"] = list(sizes.ring_tokens)
    out["mesh"] = dict(mesh.shape)

    def attn_fn(q, k, v):
        return ring_attention(q, k, v, mesh=mesh, axis="sp", causal=True,
                              head_axis="tp")

    losses = _train_steps(cfg, mesh, P("dp", "sp"), sizes.ring_tokens,
                          attn_fn, 2, clock, prefix="ring_")
    _check_losses(losses)
    out["ring_losses"] = losses
    out["peak_bytes_in_use"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]

    dp_mesh = make_mesh({"dp": 4}, devices=devices)
    chan = CollectiveChannel(dp_mesh, "dp")
    x = np.arange(4 * 1024, dtype=np.float32).reshape(4, 1024)
    with clock("all_reduce_compile_and_run_s"):
        got = np.asarray(jax.jit(chan.all_reduce)(
            jax.device_put(x, NamedSharding(dp_mesh, P("dp")))))
    # Small integers: the sum is exact in float32 whatever the order.
    if not np.array_equal(got, x.sum(axis=0)):
        raise SystemExit("chip_smoke: all_reduce over four devices is not "
                         "the sum of the shards")
    out["all_reduce"] = "exact"
    out["seconds"] = clock.seconds
    return out


def phase_multichip_native(sizes: Sizes, dry: bool) -> dict:
    import numpy as np

    from brpc_tpu.ps_remote import DevicePsShardServer, RemoteEmbedding

    clock = Timer()
    rpc, dev, out = _native_client(dry, clock)
    if dev.device_count < 4:
        raise SystemExit(f"chip_smoke: multichip needs four devices, PJRT "
                         f"reports {dev.device_count}")
    out["device_kinds"] = [dev.device_kind(i) for i in range(4)]

    # One 4-replica executable: replica r's operand is staged on device r
    # and its result must come back on device r.
    n = 1024
    x = np.arange(4 * n, dtype=np.float32).reshape(4, n)
    with clock("all_reduce_compile_s"):
        exe = dev.compile(dev.mlir("all_reduce_sum", n, 4), num_replicas=4)
    handles = [dev.stage(x[r], r) for r in range(4)]
    try:
        with clock("all_reduce_run_s"):
            outs = exe.execute(handles, nreplicas=4)
        placed = []
        for r in range(4):
            (h,) = outs[r]
            placed.append(dev.buffer_device(h))
            got = np.frombuffer(dev.fetch(h), np.float32)
            dev.release(h)
            if not np.array_equal(got, x.sum(axis=0)):
                raise SystemExit(f"chip_smoke: native all_reduce replica "
                                 f"{r} is not the sum of the operands")
    finally:
        for h in handles:
            dev.release(h)
        exe.close()
    if placed != [0, 1, 2, 3]:
        raise SystemExit(f"chip_smoke: all_reduce results landed on "
                         f"devices {placed}")
    out["all_reduce_result_devices"] = placed

    # Four shards of the one table, shard i on device i, one client.
    vocab, dim, lr = sizes.ps_vocab, sizes.ps_dim, 0.1
    out["table"] = [vocab, dim]
    with clock("reference_table_s"):
        table = np.concatenate([_shard_table(vocab, dim, i, 4, SEED)
                                for i in range(4)])
    shards = []
    emb = None
    try:
        with clock("server_init_and_stage_up_s"):
            for i in range(4):
                shards.append(DevicePsShardServer(
                    vocab, dim, i, 4, lr=lr, seed=SEED, device_client=dev,
                    device_index=i))
        out["staged_on"] = [s.resident_device() for s in shards]
        emb = RemoteEmbedding([s.address for s in shards], vocab, dim,
                              timeout_ms=120000)
        out["checks"] = _serve_and_check(emb, table, sizes.ps_ids, lr,
                                         clock)
        # After an apply each shard's live table is its scatter launch's
        # output: where PJRT says it lives is where the launch ran.
        out["launched_on"] = [s.resident_device() for s in shards]
        out["lookups_served"] = [s._read_count for s in shards]
    finally:
        if emb is not None:
            emb.close()
        for s in shards:
            s.close()
        dev.close()
    for key in ("staged_on", "launched_on"):
        if out[key] != [0, 1, 2, 3]:
            raise SystemExit(f"chip_smoke: shards 0-3 {key} devices "
                             f"{out[key]}")
    if not all(out["lookups_served"]):
        raise SystemExit(f"chip_smoke: a shard served no Lookup: "
                         f"{out['lookups_served']}")
    _assert_no_leaked_handles(rpc)
    out["leaked_handles"] = 0
    out["seconds"] = clock.seconds
    return out


PHASES = {"kernel": phase_kernel, "train": phase_train, "ps": phase_ps,
          "multichip_jax": phase_multichip_jax,
          "multichip_native": phase_multichip_native}


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------

def _run_phase(name: str, dry: bool, deadline: float) -> dict:
    """One phase as a child process (its own session, so a timeout takes
    its whole process group). Returns the phase's JSON line, parsed."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name]
    env = dict(os.environ)
    if dry:
        cmd.append("--cpu-dry-run")
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=4")
    budget = deadline - time.monotonic()
    proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE,
                            text=True, start_new_session=True)
    try:
        stdout, _ = proc.communicate(timeout=max(budget, 1.0))
        failure = (f"exit code {proc.returncode}" if proc.returncode
                   else None)
    except subprocess.TimeoutExpired:
        stdout = ""
        failure = f"no result inside the {DEADLINE_S} s the run may take"
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    print(stdout, end="", flush=True)
    if failure is None:
        try:
            return json.loads(stdout.strip().splitlines()[-1])
        except (IndexError, ValueError):
            failure = "printed no JSON line"
    note = ""
    if not dry and os.path.exists(LIBTPU_LOCKFILE):
        note = (f" ({LIBTPU_LOCKFILE} exists: a crashed owner of the chip "
                f"may have left it, and the next one can fail on it)")
    raise SystemExit(f"chip_smoke: phase {name} failed: {failure}{note}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="tiny sizes on the CPU (fake PJRT plug-in, "
                         "interpret-mode kernel); says platform cpu")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=None,
                    help="4: the multichip phases are mandatory "
                         "(default: run them when four chips are there)")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = ap.parse_args()
    dry = args.cpu_dry_run

    if args.phase:                                # child: one phase
        sys.path.insert(0, ROOT)
        out = {"phase": args.phase}
        out.update(PHASES[args.phase](DRY if dry else REAL, dry))
        print(json.dumps(out), flush=True)
        return 0

    platforms = os.environ.get("JAX_PLATFORMS", "").lower()
    if not dry and platforms and "tpu" not in platforms.split(","):
        # Refused before any child starts: nothing loads libtpu, which
        # without a chip retries for minutes.
        raise SystemExit(
            f"chip_smoke: JAX_PLATFORMS={platforms} keeps JAX off the TPU; "
            f"this run needs one (--cpu-dry-run is the CPU run)")

    deadline = time.monotonic() + DEADLINE_S
    # kernel first: the cheapest phase, and the one that tells in seconds
    # that JAX finds no TPU.
    results = []
    try:
        for name in ("kernel", "train", "ps"):
            results.append(_run_phase(name, dry, deadline))
        if args.chips == 4 or (args.chips is None and not dry
                               and results[0]["device_count"] >= 4):
            for name in ("multichip_jax", "multichip_native"):
                results.append(_run_phase(name, dry, deadline))
    except SystemExit:
        # No verdict line when no phase got as far as naming a device.
        if results:
            print(_verdict(False, results[0]), flush=True)
        raise
    print(json.dumps({"phases": [r["phase"] for r in results],
                      "claim": None}), flush=True)
    print(_verdict(True, results[0]), flush=True)
    return 0


def _verdict(ok: bool, first: dict) -> str:
    """The run's last line of output: exactly ``ok`` and the device as the
    first phase's JAX reported it."""
    return json.dumps({"ok": ok, "device": {
        "platform": first["platform"], "kind": first["device_kind"],
        "count": first["device_count"]}})


if __name__ == "__main__":
    sys.exit(main())
