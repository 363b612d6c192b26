"""Unit tests for the framework-invariant linter (brpc_tpu.analysis.lint):
each check must fire on a seeded violation and stay quiet on the fixed
form of the same code."""

import json
import os
import subprocess
import sys
import textwrap

from brpc_tpu.analysis import lint


def _lint_src(tmp_path, src, name="mod.py", checks=None):
    p = tmp_path / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return lint.lint_files([str(p)], checks)


def _by_check(findings, check):
    return [f for f in findings if f.check == check]


# ---- ctypes-contract: argtypes/restype ----

def test_undeclared_brt_symbol_flagged(tmp_path):
    fs = _lint_src(tmp_path, "lib.brt_mystery(1)\n")
    (f,) = _by_check(fs, "ctypes-contract")
    assert "brt_mystery" in f.message
    assert "argtypes and restype" in f.message
    assert f.line == 1


def test_partial_declaration_flags_missing_restype(tmp_path):
    fs = _lint_src(tmp_path, """\
        lib.brt_thing.argtypes = []
        lib.brt_thing(1)
    """)
    (f,) = _by_check(fs, "ctypes-contract")
    assert "restype" in f.message and "argtypes and" not in f.message


def test_fully_declared_symbol_clean(tmp_path):
    fs = _lint_src(tmp_path, """\
        import ctypes
        lib.brt_ok.argtypes = [ctypes.c_int]
        lib.brt_ok.restype = ctypes.c_void_p
        lib.brt_ok(1)
    """)
    assert fs == []


def test_declaration_in_sibling_file_counts(tmp_path):
    (tmp_path / "decls.py").write_text(
        "lib.brt_shared.argtypes = []\nlib.brt_shared.restype = None\n")
    (tmp_path / "use.py").write_text("x._lib.brt_shared()\n")
    assert lint.run_lint([str(tmp_path)]) == []


# ---- ctypes-contract: CFUNCTYPE pinning ----

def test_inline_cfunctype_flagged(tmp_path):
    fs = _lint_src(tmp_path, """\
        import ctypes
        _H = ctypes.CFUNCTYPE(None)
        lib.brt_reg.argtypes = [_H]
        lib.brt_reg.restype = None
        def register(lib, cb):
            lib.brt_reg(_H(cb))
    """)
    (f,) = _by_check(fs, "ctypes-contract")
    assert "inline" in f.message and "GC" in f.message


def test_unpinned_callback_flagged_and_pinned_clean(tmp_path):
    bad = """\
        import ctypes
        _H = ctypes.CFUNCTYPE(None)
        lib.brt_reg.argtypes = [_H]
        lib.brt_reg.restype = None
        class S:
            def add(self, lib):
                @_H
                def tramp():
                    pass
                lib.brt_reg(tramp)
    """
    fs = _lint_src(tmp_path, bad, name="bad.py")
    (f,) = _by_check(fs, "ctypes-contract")
    assert "tramp" in f.message and "pinned" in f.message

    good = bad.replace("lib.brt_reg(tramp)",
                       "lib.brt_reg(tramp)\n"
                       "                self._handlers.append(tramp)")
    assert _lint_src(tmp_path, good, name="good.py") == []


def test_attribute_pinning_counts(tmp_path):
    fs = _lint_src(tmp_path, """\
        import ctypes
        _H = ctypes.CFUNCTYPE(None)
        lib.brt_reg.argtypes = [_H]
        lib.brt_reg.restype = None
        class S:
            def add(self, lib):
                cb = _H(lambda: None)
                self._cb = cb
                lib.brt_reg(cb)
    """)
    assert fs == []


# ---- fiber-shared-state ----

_HANDLER_CLASS = """\
    import threading

    class Shard:
        def __init__(self, server):
            self._mu = threading.Lock()
            self.count = 0
            server.add_service("Ps", self._handle)

        def _handle(self, method, req):
            {body}
            return b""
"""


def test_unlocked_handler_mutation_flagged(tmp_path):
    fs = _lint_src(tmp_path,
                   _HANDLER_CLASS.format(body="self.count += 1"))
    (f,) = _by_check(fs, "fiber-shared-state")
    assert "Shard._handle" in f.message and "self.count" in f.message


def test_locked_handler_mutation_clean(tmp_path):
    fs = _lint_src(tmp_path, _HANDLER_CLASS.format(
        body="with self._mu:\n                self.count += 1"))
    assert _by_check(fs, "fiber-shared-state") == []


def test_ufunc_at_mutation_flagged(tmp_path):
    fs = _lint_src(tmp_path, _HANDLER_CLASS.format(
        body="np.subtract.at(self.table, req, 1)"))
    (f,) = _by_check(fs, "fiber-shared-state")
    assert "self.table" in f.message


def test_mutation_via_helper_method_flagged(tmp_path):
    src = """\
        class Shard:
            def __init__(self, server):
                server.add_service("Ps", self._handle)

            def _handle(self, method, req):
                self._serve(req)
                return b""

            def _serve(self, req):
                self.rows.append(req)
    """
    fs = _lint_src(tmp_path, src)
    (f,) = _by_check(fs, "fiber-shared-state")
    assert "Shard._serve" in f.message


def test_helper_only_called_under_lock_clean(tmp_path):
    src = """\
        import threading

        class Shard:
            def __init__(self, server):
                self._mu = threading.Lock()
                server.add_service("Ps", self._handle)

            def _handle(self, method, req):
                with self._mu:
                    self._serve(req)
                return b""

            def _serve(self, req):
                self.rows = req
    """
    assert _lint_src(tmp_path, src) == []


def test_non_handler_class_not_audited(tmp_path):
    src = """\
        class Plain:
            def poke(self):
                self.count = 1
    """
    assert _lint_src(tmp_path, src) == []


# ---- fiber-shared-state: rwlock read()/write() contexts ----

_RW_HANDLER = """\
    from brpc_tpu.analysis.race import checked_rwlock

    class Shard:
        def __init__(self, server):
            self._mu = checked_rwlock("t.shard")
            self.count = 0
            server.add_service("Ps", self._handle)

        def _handle(self, method, req):
            {body}
            return b""
"""


def test_mutation_under_write_side_clean(tmp_path):
    fs = _lint_src(tmp_path, _RW_HANDLER.format(
        body="with self._mu.write():\n                self.count += 1"))
    assert _by_check(fs, "fiber-shared-state") == []


def test_mutation_under_read_side_flagged(tmp_path):
    """The read side is SHARED — it must never legitimize mutation."""
    fs = _lint_src(tmp_path, _RW_HANDLER.format(
        body="with self._mu.read():\n                self.count += 1"))
    (f,) = _by_check(fs, "fiber-shared-state")
    assert "self.count" in f.message
    assert "read-side" in f.message and "write" in f.message


def test_read_only_access_under_read_side_clean(tmp_path):
    fs = _lint_src(tmp_path, _RW_HANDLER.format(
        body="with self._mu.read():\n                x = self.count"))
    assert _by_check(fs, "fiber-shared-state") == []


def test_read_side_does_not_propagate_as_lock_through_calls(tmp_path):
    src = """\
        from brpc_tpu.analysis.race import checked_rwlock

        class Shard:
            def __init__(self, server):
                self._mu = checked_rwlock("t.shard")
                server.add_service("Ps", self._handle)

            def _handle(self, method, req):
                with self._mu.read():
                    self._bump()
                return b""

            def _bump(self):
                self.count = 1
    """
    fs = _lint_src(tmp_path, src)
    (f,) = _by_check(fs, "fiber-shared-state")
    assert "Shard._bump" in f.message


# ---- obs-guard ----

def test_direct_registry_use_flagged(tmp_path):
    fs = _lint_src(tmp_path, """\
        from brpc_tpu import obs

        def hot(n):
            obs.counter("x").add(n)      # allowed: no-op-able helper
            a = obs.Adder()              # direct reducer construction
            obs.default_registry()       # direct registry access
            obs.expose("y", a)           # direct expose
    """)
    fs = _by_check(fs, "obs-guard")
    assert len(fs) == 3
    assert all("no-op-able" in f.message for f in fs)


def test_obs_package_itself_exempt(tmp_path):
    fs = _lint_src(tmp_path, """\
        from brpc_tpu import obs
        obs.Adder()
    """, name=os.path.join("obs", "inner.py"))
    assert _by_check(fs, "obs-guard") == []


# ---- trace-purity ----

def test_impure_jit_function_flagged(tmp_path):
    fs = _lint_src(tmp_path, """\
        import time
        import jax
        from functools import partial
        from brpc_tpu import obs

        @jax.jit
        def step(x):
            print(x)
            t = time.time()
            return x + t

        @partial(jax.jit, static_argnames=())
        def counted(x):
            obs.counter("steps").add(1)
            return x

        traced = jax.jit(lambda x: print(x))
    """)
    fs = _by_check(fs, "trace-purity")
    assert len(fs) == 4
    kinds = " | ".join(f.message for f in fs)
    assert "print" in kinds and "time.time" in kinds and "obs" in kinds


def test_shard_map_lock_flagged(tmp_path):
    fs = _lint_src(tmp_path, """\
        from functools import partial
        from jax import shard_map

        class C:
            def op(self, x):
                @partial(shard_map, mesh=self.mesh, in_specs=None,
                         out_specs=None)
                def _f(shard):
                    with self._mu:
                        return shard
                return _f(x)
    """)
    (f,) = _by_check(fs, "trace-purity")
    assert "lock" in f.message


def test_pure_jit_function_clean(tmp_path):
    fs = _lint_src(tmp_path, """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            return jnp.sum(x * 2)
    """)
    assert fs == []


# ---- trace-purity: host callbacks under trace ----

def test_host_callback_flagged_and_pragma_allowlists(tmp_path):
    fs = _lint_src(tmp_path, """\
        import jax

        @jax.jit
        def noisy(x):
            jax.debug.print("x={}", x)
            return x

        @jax.jit
        def wanted(x):
            jax.debug.print("x={}", x)  # lint: allow-host-callback
            return jax.pure_callback(lambda v: v, x, x)
    """)
    fs = _by_check(fs, "trace-purity")
    assert len(fs) == 2
    msgs = " | ".join(f.message for f in fs)
    assert "jax.debug.print" in msgs and "pure_callback" in msgs
    assert all("host round-trip" in f.message for f in fs)
    # the allowlisted debug.print on its own line did NOT fire
    assert not any(f.line == 10 for f in fs)


def test_host_callback_transitive_chain(tmp_path):
    fs = _lint_src(tmp_path, """\
        import jax

        def helper(x):
            return jax.experimental.io_callback(lambda v: v, x, x)

        @jax.jit
        def step(x):
            return helper(x)
    """)
    (f,) = _by_check(fs, "trace-purity")
    assert "io_callback" in f.message
    assert "step -> helper" in f.message


# ---- lock-order (static inversion cycles) ----

_LOCK_FIXTURE = """\
    from brpc_tpu.analysis.race import checked_lock

    lock_a = checked_lock("fix.A")
    lock_b = checked_lock("fix.B")

    def order_ab():
        with lock_a:
            take_b()

    def take_b():
        with lock_b:
            pass

    def order_ba():
        with lock_b:
            with lock_a:
                pass
"""


def test_static_lock_order_inversion(tmp_path):
    fs = _lint_src(tmp_path, _LOCK_FIXTURE)
    (f,) = _by_check(fs, "lock-order")
    assert "fix.A" in f.message and "fix.B" in f.message
    assert "deadlock" in f.message
    # both acquisition contexts are named, incl. the call chain
    assert "order_ab -> take_b" in f.message
    assert "order_ba" in f.message


def test_static_lock_order_consistent_nesting_clean(tmp_path):
    fs = _lint_src(tmp_path, """\
        from brpc_tpu.analysis.race import checked_lock

        lock_a = checked_lock("ok.A")
        lock_b = checked_lock("ok.B")

        def one():
            with lock_a:
                with lock_b:
                    pass

        def two():
            with lock_a:
                with lock_b:
                    pass
    """)
    assert _by_check(fs, "lock-order") == []


def test_static_lock_order_instance_locks(tmp_path):
    fs = _lint_src(tmp_path, """\
        from brpc_tpu.analysis.race import checked_lock

        class S:
            def __init__(self):
                self._mu = checked_lock("inst.A")
                self._table_mu = checked_lock("inst.B")

            def fwd(self):
                with self._mu:
                    with self._table_mu:
                        pass

            def rev(self):
                with self._table_mu:
                    with self._mu:
                        pass
    """)
    (f,) = _by_check(fs, "lock-order")
    assert "inst.A" in f.message and "inst.B" in f.message


_RW_LOCK_FIXTURE = """\
    from brpc_tpu.analysis.race import checked_lock, checked_rwlock

    rw = checked_rwlock("rwfix.A")
    mu = checked_lock("rwfix.B")

    def read_then_lock():
        with rw.read():
            with mu:
                pass

    def lock_then_write():
        with mu:
            with rw.write():
                pass
"""


def test_static_lock_order_sees_rwlock_sides(tmp_path):
    """checked_rwlock's read()/write() contexts acquire under the lock's
    one name, so a read-vs-write inversion against another lock is a
    static cycle — parity with the dynamic harness's keying."""
    fs = _lint_src(tmp_path, _RW_LOCK_FIXTURE)
    (f,) = _by_check(fs, "lock-order")
    assert "rwfix.A" in f.message and "rwfix.B" in f.message
    assert "deadlock" in f.message


def test_static_lock_order_rwlock_consistent_order_clean(tmp_path):
    fs = _lint_src(tmp_path, """\
        from brpc_tpu.analysis.race import checked_lock, checked_rwlock

        rw = checked_rwlock("rwok.A")
        mu = checked_lock("rwok.B")

        def reader():
            with rw.read():
                with mu:
                    pass

        def writer():
            with rw.write():
                with mu:
                    pass
    """)
    assert _by_check(fs, "lock-order") == []


def test_static_rwlock_inversion_matches_dynamic_harness(tmp_path):
    from brpc_tpu.analysis import race

    static = _by_check(_lint_src(tmp_path, _RW_LOCK_FIXTURE), "lock-order")
    assert len(static) == 1

    race.clear()
    race.set_enabled(True)
    try:
        ns = {"checked_lock": race.checked_lock,
              "checked_rwlock": race.checked_rwlock}
        exec(textwrap.dedent(_RW_LOCK_FIXTURE).split("\n", 1)[1], ns)
        ns["read_then_lock"]()
        ns["lock_then_write"]()
        dynamic = [f for f in race.findings()
                   if f.kind == "lock-inversion"]
    finally:
        race.set_enabled(None)
        race.clear()
    assert len(dynamic) == 1
    assert {"rwfix.A", "rwfix.B"} <= set(dynamic[0].locks)


def test_static_lock_order_matches_dynamic_harness(tmp_path):
    """The acceptance contract: the static pass reproduces the dynamic
    harness's inversion finding on the same fixture — RACECHECK becomes
    the confirmer, not the only detector."""
    from brpc_tpu.analysis import race

    static = _by_check(_lint_src(tmp_path, _LOCK_FIXTURE), "lock-order")
    assert len(static) == 1
    static_locks = {n for n in ("fix.A", "fix.B")
                    if n in static[0].message}

    race.clear()
    race.set_enabled(True)
    try:
        ns = {"checked_lock": race.checked_lock}
        exec(textwrap.dedent(_LOCK_FIXTURE).split("\n", 1)[1], ns)
        ns["order_ab"]()
        ns["order_ba"]()
        dynamic = [f for f in race.findings()
                   if f.kind == "lock-inversion"]
    finally:
        race.set_enabled(None)
        race.clear()
    assert len(dynamic) == 1
    assert static_locks == {"fix.A", "fix.B"} <= set(dynamic[0].locks)


# ---- stable finding ids + baseline ----

def test_finding_id_stable_under_line_drift(tmp_path):
    (f1,) = _lint_src(tmp_path, "lib.brt_bad(1)\n", name="v1.py")
    (f2,) = _lint_src(tmp_path, "# a comment pushing the line\n"
                                "\nlib.brt_bad(1)\n", name="v1.py")
    assert f1.line != f2.line
    assert f1.id == f2.id  # id hashes check+path+message, not the line


def test_finding_id_differs_across_checks_and_files(tmp_path):
    (a,) = _lint_src(tmp_path, "lib.brt_one(1)\n", name="a.py")
    (b,) = _lint_src(tmp_path, "lib.brt_one(1)\n", name="b.py")
    assert a.id != b.id


def test_apply_baseline_split():
    f = lint.Finding("ctypes-contract", "x.py", 1, "msg")
    g = lint.Finding("ctypes-contract", "x.py", 2, "other msg")
    new, old = lint.apply_baseline([f, g], {f.id})
    assert new == [g] and old == [f]


# ---- check selection + CLI ----

def test_unknown_check_rejected(tmp_path):
    try:
        _lint_src(tmp_path, "x = 1\n", checks=["no-such-check"])
    except ValueError as e:
        assert "no-such-check" in str(e)
        assert "valid checks" in str(e)
        for name in lint.ALL_CHECKS:
            assert name in str(e)
    else:
        raise AssertionError("expected ValueError")


def test_check_filter(tmp_path):
    src = """\
        lib.brt_x()
    """
    assert _lint_src(tmp_path, src, checks=["obs-guard"]) == []
    assert len(_lint_src(tmp_path, src, checks=["ctypes-contract"])) == 1


def _run_cli(args, cwd):
    env = dict(os.environ)
    env["PYTHONPATH"] = cwd + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "brpc_tpu.analysis"] + args,
        capture_output=True, text=True, env=env, cwd=cwd)


def test_cli_exit_codes_and_json(tmp_path):
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(lint.__file__))))
    bad = tmp_path / "viol.py"
    bad.write_text("lib.brt_bad(1)\n")
    proc = _run_cli([str(bad), "--format=json"], cwd=repo)
    assert proc.returncode == 1, proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["count"] == 1
    (f,) = payload["findings"]
    assert f["check"] == "ctypes-contract" and f["line"] == 1
    assert f["path"].endswith("viol.py")

    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    proc = _run_cli([str(clean)], cwd=repo)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stderr


def test_cli_text_format_has_file_line(tmp_path):
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(lint.__file__))))
    bad = tmp_path / "viol.py"
    bad.write_text("\nlib.brt_bad(1)\n")
    proc = _run_cli([str(bad)], cwd=repo)
    assert proc.returncode == 1
    assert f"{bad}:2:" in proc.stdout


def test_cli_unknown_check_exits_2_and_lists_valid_set(tmp_path):
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(lint.__file__))))
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    proc = _run_cli([str(clean), "--check", "trace_purity"], cwd=repo)
    assert proc.returncode == 2
    assert "trace_purity" in proc.stderr
    for name in lint.ALL_CHECKS:
        assert name in proc.stderr  # the valid set is listed


def test_cli_baseline_suppression_roundtrip(tmp_path):
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(lint.__file__))))
    bad = tmp_path / "viol.py"
    bad.write_text("lib.brt_bad(1)\n")
    base = tmp_path / "baseline.json"
    proc = _run_cli([str(bad), "--write-baseline", str(base)], cwd=repo)
    assert proc.returncode == 0, proc.stderr
    # known finding suppressed -> clean exit
    proc = _run_cli([str(bad), "--baseline", str(base)], cwd=repo)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "suppressed by baseline" in proc.stderr
    # a NEW finding still fails even with the baseline applied
    bad.write_text("lib.brt_bad(1)\nlib.brt_worse(2)\n")
    proc = _run_cli([str(bad), "--baseline", str(base), "--format=json"],
                    cwd=repo)
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["count"] == 1
    assert payload["suppressed_count"] == 1
    assert "brt_worse" in payload["findings"][0]["message"]


def test_syntax_error_reported_not_crash(tmp_path):
    fs = _lint_src(tmp_path, "def broken(:\n")
    (f,) = fs
    assert f.check == "syntax"


# ---- fiber-blocking-sleep (interprocedural) ----

_SLEEP_HANDLER = """\
    import time

    class S:
        def __init__(self, server):
            server.add_service("X", self._handle)

        def _handle(self, method, req):
            time.sleep(0.5)
            return b""
"""


def test_handler_sleep_flagged(tmp_path):
    fs = _lint_src(tmp_path, _SLEEP_HANDLER)
    (f,) = _by_check(fs, "fiber-blocking-sleep")
    assert "time.sleep" in f.message
    assert "fiber worker" in f.message
    assert "resilience" in f.message


def test_sleep_via_helper_chain_flagged(tmp_path):
    fs = _lint_src(tmp_path, """\
        import time

        def pause():
            time.sleep(0.1)

        def work():
            pause()

        class S:
            def __init__(self, server):
                server.add_service("X", self._handle)

            def _handle(self, method, req):
                work()
                return b""
    """)
    (f,) = _by_check(fs, "fiber-blocking-sleep")
    assert "pause" in f.message
    assert "S._handle -> work -> pause" in f.message


def test_sleep_alias_forms_flagged(tmp_path):
    fs = _lint_src(tmp_path, """\
        import time as t
        from time import sleep as zzz

        class S:
            def __init__(self, server):
                server.add_service("X", self._handle)

            def _handle(self, method, req):
                t.sleep(1)
                zzz(2)
                return b""
    """)
    fs = _by_check(fs, "fiber-blocking-sleep")
    assert len(fs) == 2
    assert any("imported from time" in f.message for f in fs)


def test_sleep_outside_handlers_clean(tmp_path):
    fs = _lint_src(tmp_path, """\
        import time

        def bench_loop():
            time.sleep(1.0)  # not handler-reachable: fine

        class S:
            def __init__(self, server):
                server.add_service("X", self._handle)

            def _handle(self, method, req):
                return b""
    """)
    assert _by_check(fs, "fiber-blocking-sleep") == []


def test_sleep_via_resilience_helper_clean(tmp_path):
    # The sanctioned path: resilience.sleep_ms — the call into the
    # resilience module is not followed, and a fake sibling named
    # resilience.py proves the cut is by module path, not luck.
    (tmp_path / "brpc_tpu").mkdir()
    (tmp_path / "brpc_tpu" / "__init__.py").write_text("")
    (tmp_path / "brpc_tpu" / "resilience.py").write_text(
        "import time\n\ndef sleep_ms(ms):\n    time.sleep(ms / 1000.0)\n")
    (tmp_path / "brpc_tpu" / "svc.py").write_text(textwrap.dedent("""\
        from brpc_tpu.resilience import sleep_ms

        class S:
            def __init__(self, server):
                server.add_service("X", self._handle)

            def _handle(self, method, req):
                sleep_ms(5)
                return b""
    """))
    fs = lint.run_lint([str(tmp_path / "brpc_tpu")])
    assert _by_check(fs, "fiber-blocking-sleep") == []


def test_async_handler_sleep_flagged(tmp_path):
    fs = _lint_src(tmp_path, """\
        import time

        class S:
            def __init__(self, server):
                server.add_async_service("X", self._handle)

            def _handle(self, method, req, respond):
                time.sleep(0.2)
                respond(b"")
    """)
    (f,) = _by_check(fs, "fiber-blocking-sleep")
    assert "S._handle" in f.message


# ---- ctypes-contract: module-scope / global pinning refinements ----

def test_module_level_callback_is_pinned_by_the_module(tmp_path):
    # a module-level CFUNCTYPE def is held by the module namespace for
    # the life of the process — it cannot be GC'd under the native core
    fs = _lint_src(tmp_path, """\
        import ctypes
        _H = ctypes.CFUNCTYPE(None)
        lib.brt_reg.argtypes = [_H]
        lib.brt_reg.restype = None

        @_H
        def dispatch():
            pass

        def install(lib):
            lib.brt_reg(dispatch)
    """)
    assert _by_check(fs, "ctypes-contract") == []


def test_global_assignment_pins_callback(tmp_path):
    good = """\
        import ctypes
        _H = ctypes.CFUNCTYPE(None)
        lib.brt_reg.argtypes = [_H]
        lib.brt_reg.restype = None
        _ref = None

        def install(lib):
            global _ref

            @_H
            def hook():
                pass
            _ref = hook
            lib.brt_reg(hook)
    """
    assert _by_check(_lint_src(tmp_path, good, name="good.py"),
                     "ctypes-contract") == []
    # without the global pin the function-local callback is still flagged
    bad = textwrap.dedent(good).replace("    global _ref\n", "") \
                               .replace("    _ref = hook\n", "")
    assert bad != textwrap.dedent(good)
    (tmp_path / "good.py").write_text(bad)
    findings = _by_check(lint.lint_files([str(tmp_path / "good.py")]),
                         "ctypes-contract")
    assert len(findings) == 1 and "hook" in findings[0].message


# ---- trace-purity: the allow-trace-impure pragma ----

_TRACED_WITH_COUNTER = """\
    import jax
    from brpc_tpu import obs

    def _count(op):{pragma_def}
        obs.counter(op).add(1)

    def step(x):
        _count("steps"){pragma_call}
        return x

    run = jax.jit(step)
"""


def test_deliberate_trace_time_effect_flagged_without_pragma(tmp_path):
    fs = _lint_src(tmp_path,
                   _TRACED_WITH_COUNTER.format(pragma_def="",
                                               pragma_call=""))
    assert any("obs instrumentation" in f.message
               for f in _by_check(fs, "trace-purity"))


def test_def_level_allow_trace_impure_pragma(tmp_path):
    fs = _lint_src(tmp_path, _TRACED_WITH_COUNTER.format(
        pragma_def="  # lint: allow-trace-impure", pragma_call=""))
    assert _by_check(fs, "trace-purity") == []


def test_call_site_allow_trace_impure_pragma(tmp_path):
    fs = _lint_src(tmp_path, _TRACED_WITH_COUNTER.format(
        pragma_def="", pragma_call="  # lint: allow-trace-impure"))
    assert _by_check(fs, "trace-purity") == []


# ---- lock-order: param-passed locks bound through the call graph ----

_PARAM_LOCK_FIXTURE = """\
    from brpc_tpu.analysis.race import checked_lock
    A = checked_lock("pfix.A")
    B = checked_lock("pfix.B")

    def use_inner(lk):
        with lk:
            pass

    def order_ab():
        with A:
            use_inner(B)

    def order_ba():
        with B:
            with A:
                pass
"""


def test_static_lock_order_resolves_param_passed_lock(tmp_path):
    static = _by_check(_lint_src(tmp_path, _PARAM_LOCK_FIXTURE),
                       "lock-order")
    assert len(static) == 1
    assert "pfix.A" in static[0].message and "pfix.B" in static[0].message
    assert "use_inner" in static[0].message  # the chain names the callee


def test_param_passed_lock_matches_dynamic_harness(tmp_path):
    """Parity on the PR-3 blind spot: a lock received as a function
    parameter now resolves statically by binding the caller's argument
    through the call graph — the dynamic harness stays the confirmer."""
    from brpc_tpu.analysis import race

    static = _by_check(_lint_src(tmp_path, _PARAM_LOCK_FIXTURE),
                       "lock-order")
    assert len(static) == 1

    race.clear()
    race.set_enabled(True)
    try:
        ns = {"checked_lock": race.checked_lock}
        src = textwrap.dedent(_PARAM_LOCK_FIXTURE)
        exec(src.split("\n", 1)[1], ns)
        ns["order_ab"]()
        ns["order_ba"]()
        dynamic = [f for f in race.findings()
                   if f.kind == "lock-inversion"]
    finally:
        race.set_enabled(None)
        race.clear()
    assert len(dynamic) == 1
    assert {"pfix.A", "pfix.B"} <= set(dynamic[0].locks)


def test_param_lock_keyword_argument_binds(tmp_path):
    fs = _lint_src(tmp_path, """\
        from brpc_tpu.analysis.race import checked_lock
        A = checked_lock("kw.A")
        B = checked_lock("kw.B")

        def helper(*, lk=None):
            with lk:
                pass

        def outer():
            with B:
                helper(lk=A)

        def reverse():
            with A:
                with B:
                    pass
    """)
    (f,) = _by_check(fs, "lock-order")
    assert "kw.A" in f.message and "kw.B" in f.message


_CONTAINER_LOCK_FIXTURE = """\
    from brpc_tpu.analysis.race import checked_lock
    A = checked_lock("cd.A")
    B = checked_lock("cd.B")
    LOCKS = {"a": A, "b": checked_lock("cd.C")}

    def inner():
        with LOCKS["a"]:
            pass

    def outer():
        with B:
            inner()

    def reverse():
        with A:
            with B:
                pass
"""


def test_container_stored_lock_resolves(tmp_path):
    # the last PR-3 lock blind spot, now closed: a lock pulled out of a
    # MODULE-LEVEL LITERAL dict resolves by subscript key — both
    # name-valued ({"a": A}) and direct checked_lock(...) entries
    fs = _lint_src(tmp_path, _CONTAINER_LOCK_FIXTURE)
    (f,) = _by_check(fs, "lock-order")
    assert "cd.A" in f.message and "cd.B" in f.message
    assert "inner" in f.message  # the chain names the callee


def test_container_stored_lock_matches_dynamic_harness(tmp_path):
    """Parity: the container-lock inversion the static pass now reports
    is exactly the one the dynamic harness observes at runtime."""
    from brpc_tpu.analysis import race

    static = _by_check(_lint_src(tmp_path, _CONTAINER_LOCK_FIXTURE),
                       "lock-order")
    assert len(static) == 1

    race.clear()
    race.set_enabled(True)
    try:
        ns = {"checked_lock": race.checked_lock}
        exec(textwrap.dedent(_CONTAINER_LOCK_FIXTURE).split("\n", 1)[1],
             ns)
        ns["outer"]()
        ns["reverse"]()
        dynamic = [f for f in race.findings()
                   if f.kind == "lock-inversion"]
    finally:
        race.set_enabled(None)
        race.clear()
    assert len(dynamic) == 1
    assert {"cd.A", "cd.B"} <= set(dynamic[0].locks)


def test_container_lock_non_constant_key_stays_deferred(tmp_path):
    # a dynamic key cannot bind statically — no false edges, no finding
    fs = _lint_src(tmp_path, """\
        from brpc_tpu.analysis.race import checked_lock
        A = checked_lock("cdk.A")
        B = checked_lock("cdk.B")
        LOCKS = {"a": A}

        def inner(k):
            with LOCKS[k]:
                pass

        def outer():
            with B:
                inner("a")

        def reverse():
            with A:
                with B:
                    pass
    """)
    assert _by_check(fs, "lock-order") == []


def test_container_lock_mutated_container_stays_deferred(tmp_path):
    # only LITERAL module dicts participate: a container built by
    # subscript stores is not trusted (its contents are runtime state)
    fs = _lint_src(tmp_path, """\
        from brpc_tpu.analysis.race import checked_lock
        A = checked_lock("cm.A")
        B = checked_lock("cm.B")
        LOCKS = {}
        LOCKS["a"] = A

        def inner():
            with LOCKS["a"]:
                pass

        def outer():
            with B:
                inner()

        def reverse():
            with A:
                with B:
                    pass
    """)
    assert _by_check(fs, "lock-order") == []


# ---- handle-lifecycle ----

_RPC_STUB = """\
    class RpcError(RuntimeError):
        pass


    class PendingCall:
        def __init__(self):
            self._ptr = 1

        def join(self):
            return b""

        def wait(self, t=None):
            return True

        def cancel(self):
            pass

        def close(self):
            pass


    class Stream:
        def __init__(self):
            self._id = 1

        def write(self, data):
            pass

        def close(self):
            pass

        def join(self, timeout_s=None):
            return True

        def abort(self):
            pass


    class Channel:
        def __init__(self, addr):
            self._ptr = 1

        def call_async(self, service, method, request=b""):
            return PendingCall()

        def stream(self, service, method, request=b""):
            return Stream()

        def close(self):
            pass


    class Server:
        def __init__(self):
            self._ptr = 1

        def close(self):
            pass
"""


def _lint_handle_fixture(tmp_path, app_src, name="app.py"):
    (tmp_path / "rpc.py").write_text(textwrap.dedent(_RPC_STUB))
    (tmp_path / name).write_text(textwrap.dedent(app_src))
    return lint.run_lint([str(tmp_path)], checks=["handle-lifecycle"])


def test_dropped_pending_call_flagged(tmp_path):
    fs = _lint_handle_fixture(tmp_path, """\
        def fire_and_forget(ch):
            ch.call_async("Ps", "ApplyGrad", b"x")
    """)
    (f,) = fs
    assert "PendingCall" in f.message and "DROPPED" in f.message
    assert f.line == 2


def test_unclosed_stream_on_early_return_path_flagged(tmp_path):
    fs = _lint_handle_fixture(tmp_path, """\
        import rpc

        def push(addr, flag):
            ch = rpc.Channel(addr)
            st = ch.stream("Ps", "StreamApply")
            if flag:
                ch.close()
                return None
            st.write(b"delta")
            st.close()
            ch.close()
    """)
    (f,) = fs
    assert "Stream 'st'" in f.message and "leaks" in f.message
    assert f.line == 8  # the early return, not the binding


def test_clean_ownership_transfer_is_clean(tmp_path):
    fs = _lint_handle_fixture(tmp_path, """\
        import rpc
        from rpc import Channel


        def make_channel(addr):
            return Channel(addr)


        def round_trip(addr):
            ch = make_channel(addr)
            try:
                pc = ch.call_async("Echo", "M")
                return pc.join()
            finally:
                ch.close()


        class Holder:
            def __init__(self, addr):
                self.ch = rpc.Channel(addr)
                self.srv = rpc.Server()

            def close(self):
                self.ch.close()
                self.srv.close()
    """)
    assert fs == []


def test_inline_consumed_factory_chain_is_clean(tmp_path):
    fs = _lint_handle_fixture(tmp_path, """\
        def call(ch, req):
            return ch.call_async("S", "M", req).join()
    """)
    assert fs == []


def test_attr_store_without_release_method_flagged(tmp_path):
    fs = _lint_handle_fixture(tmp_path, """\
        import rpc


        class LeakyHolder:
            def __init__(self, addr):
                self.ch = rpc.Channel(addr)
    """)
    (f,) = fs
    assert "LeakyHolder.ch" in f.message
    assert "never releases" in f.message


def test_container_escape_flagged_and_pragma_accepted(tmp_path):
    bad = """\
        import rpc

        def pool(addrs):
            out = {}
            for i, a in enumerate(addrs):
                out[i] = rpc.Channel(a)
            return out
    """
    (f,) = _lint_handle_fixture(tmp_path, bad)
    assert "container" in f.message and "allow-handle-escape" in f.message
    good = bad.replace(
        "out[i] = rpc.Channel(a)",
        "out[i] = rpc.Channel(a)  # lint: allow-handle-escape")
    assert _lint_handle_fixture(tmp_path, good) == []


def test_thread_target_escape_flagged(tmp_path):
    fs = _lint_handle_fixture(tmp_path, """\
        import threading

        import rpc

        def spawn(addr):
            ch = rpc.Channel(addr)
            t = threading.Thread(target=worker, args=(ch,))
            t.start()

        def worker(ch):
            pass
    """)
    (f,) = fs
    assert "thread target" in f.message


def test_fall_through_leak_flagged_and_release_any_branch_clean(tmp_path):
    (f,) = _lint_handle_fixture(tmp_path, """\
        import rpc

        def leaky(addr):
            ch = rpc.Channel(addr)
            ch.call_async("S", "M").join()
    """)
    assert "Channel 'ch'" in f.message and "fall-through" in f.message
    # may-leak polarity: a release on SOME branch is trusted (the guard
    # idiom) — no false positive
    assert _lint_handle_fixture(tmp_path, """\
        import rpc

        def guarded(addr, cond):
            ch = rpc.Channel(addr)
            if cond:
                ch.close()
    """) == []


def test_finally_release_covers_returns_inside_try(tmp_path):
    assert _lint_handle_fixture(tmp_path, """\
        import rpc

        def fan_out(addr, reqs):
            group = rpc.Server()
            try:
                for r in reqs:
                    if not r:
                        return None
                return len(reqs)
            finally:
                group.close()
    """) == []


def test_abi_pairing_requires_destroy_symbol(tmp_path):
    fs = _lint_src(tmp_path, """\
        import ctypes
        lib.brt_widget_new.argtypes = []
        lib.brt_widget_new.restype = ctypes.c_void_p
        lib.brt_widget_new()
    """, checks=["handle-lifecycle"])
    (f,) = fs
    assert "brt_widget_destroy" in f.message
    fixed = _lint_src(tmp_path, """\
        import ctypes
        lib.brt_widget_new.argtypes = []
        lib.brt_widget_new.restype = ctypes.c_void_p
        lib.brt_widget_destroy.argtypes = [ctypes.c_void_p]
        lib.brt_widget_destroy.restype = None
        lib.brt_widget_new()
    """, name="fixed.py", checks=["handle-lifecycle"])
    assert fixed == []


# ---- lock-order: class-scope literal-dict containers ----

_CLASS_CONTAINER_LOCK_FIXTURE = """\
    from brpc_tpu.analysis.race import checked_lock

    class Engine:
        LOCKS = {"a": checked_lock("ccd.A"), "b": checked_lock("ccd.B")}

        def fwd(self):
            with self.LOCKS["a"]:
                with self.LOCKS["b"]:
                    pass

        def rev(self):
            with self.LOCKS["b"]:
                with self.LOCKS["a"]:
                    pass
"""


def test_class_container_stored_lock_resolves(tmp_path):
    # `self.LOCKS["a"]` on a CLASS-scope literal dict binds by constant
    # key, same as the module-level container form
    fs = _lint_src(tmp_path, _CLASS_CONTAINER_LOCK_FIXTURE)
    (f,) = _by_check(fs, "lock-order")
    assert "ccd.A" in f.message and "ccd.B" in f.message


def test_class_container_lock_matches_dynamic_harness(tmp_path):
    """Parity: the class-container inversion the static pass now
    reports is exactly the one the dynamic harness observes."""
    import textwrap as _tw

    from brpc_tpu.analysis import race

    static = _by_check(_lint_src(tmp_path,
                                 _CLASS_CONTAINER_LOCK_FIXTURE),
                       "lock-order")
    assert len(static) == 1

    race.clear()
    race.set_enabled(True)
    try:
        ns = {"checked_lock": race.checked_lock}
        exec(_tw.dedent(_CLASS_CONTAINER_LOCK_FIXTURE).split("\n", 1)[1],
             ns)
        eng = ns["Engine"]()
        eng.fwd()
        eng.rev()
        dynamic = [f for f in race.findings()
                   if f.kind == "lock-inversion"]
    finally:
        race.set_enabled(None)
        race.clear()
    assert len(dynamic) == 1
    assert {"ccd.A", "ccd.B"} <= set(dynamic[0].locks)


def test_class_container_non_constant_key_stays_deferred(tmp_path):
    fs = _lint_src(tmp_path, """\
        from brpc_tpu.analysis.race import checked_lock

        class Engine:
            LOCKS = {"a": checked_lock("cck.A")}
            B = None

        OTHER = checked_lock("cck.B")

        def use(eng, k):
            with OTHER:
                with eng.LOCKS[k]:
                    pass

        def reverse(eng):
            with eng.LOCKS["a"]:
                with OTHER:
                    pass
    """)
    assert _by_check(fs, "lock-order") == []


# ---- handle-lifecycle: exception paths (raise = an exit) ----

def test_handle_live_at_raise_flagged(tmp_path):
    fs = _lint_handle_fixture(tmp_path, """\
        import rpc

        def leaky(addr, payload):
            ch = rpc.Channel(addr)
            if not payload:
                raise ValueError("empty payload")
            ch.close()
    """)
    (f,) = fs
    assert "raise (exception path)" in f.message
    assert "'ch'" in f.message and "created line 4" in f.message


def test_handle_released_by_catching_except_clean(tmp_path):
    # the handler that catches the raise releases (and may re-raise
    # after cleanup): the exception path is covered
    assert _lint_handle_fixture(tmp_path, """\
        import rpc

        def covered(addr, payload):
            ch = rpc.Channel(addr)
            try:
                if not payload:
                    raise ValueError("bad")
            except ValueError:
                ch.close()
                raise
            ch.close()
    """) == []


def test_handle_released_by_finally_at_raise_clean(tmp_path):
    assert _lint_handle_fixture(tmp_path, """\
        import rpc

        def covered(addr, payload):
            ch = rpc.Channel(addr)
            try:
                if not payload:
                    raise ValueError("bad")
                return ch.call_async("S", "m").join()
            finally:
                ch.close()
    """) == []


def test_raise_in_else_clause_not_covered_by_handlers(tmp_path):
    # except handlers do NOT catch raises from the else clause: a
    # release that lives only in the handler does not cover this path
    fs = _lint_handle_fixture(tmp_path, """\
        import rpc

        def leaky(addr, payload):
            ch = rpc.Channel(addr)
            try:
                n = len(payload)
            except TypeError:
                ch.close()
                raise
            else:
                if n == 0:
                    raise ValueError("empty")
            ch.close()
    """)
    (f,) = fs
    assert "raise (exception path)" in f.message


def test_raise_after_release_clean(tmp_path):
    assert _lint_handle_fixture(tmp_path, """\
        import rpc

        def strict(addr, payload):
            ch = rpc.Channel(addr)
            if not payload:
                ch.close()
                raise ValueError("empty payload")
            ch.close()
    """) == []


# ---- exception-flow: implicit throws from callees are exits, proven
# ---- statically AND reproduced on the BRPC_TPU_HANDLECHECK ledger ----

import itertools as _it
import types as _types

import pytest

from brpc_tpu.analysis import handles as _handles
from brpc_tpu.analysis import race as _race


def _lint_exc_fixture(tmp_path, app_src, name="app.py"):
    """Static half: handle-lifecycle + the exception-flow tier built on
    the may-throw fixpoint."""
    (tmp_path / "rpc.py").write_text(textwrap.dedent(_RPC_STUB))
    (tmp_path / name).write_text(textwrap.dedent(app_src))
    return lint.run_lint([str(tmp_path)],
                         checks=["handle-lifecycle", "exception-flow"])


def _ledger_rpc_module():
    """An ``rpc`` twin whose owner classes book every construct/release
    in the HANDLECHECK ledger — the runtime half of the static/dynamic
    parity below runs the SAME fixture source against it."""
    seq = _it.count(0x4000)
    mod = _types.ModuleType("rpc")

    class PendingCall:
        def __init__(self):
            self._h = next(seq)
            _handles.note_create("pending", self._h)

        def join(self):
            _handles.note_destroy("pending", self._h)
            return b""

    class Channel:
        def __init__(self, addr):
            self._h = next(seq)
            _handles.note_create("chan", self._h)

        def call_async(self, service, method, request=b""):
            return PendingCall()

        def close(self):
            _handles.note_destroy("chan", self._h)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            self.close()
            return False

    mod.PendingCall = PendingCall
    mod.Channel = Channel
    return mod


def _ledger_run(fixture, call=None, expect=None):
    """Exec ``fixture`` (its ``import rpc`` redirected to the ledger
    twin), optionally invoke ``call=(fn, *args)`` (expecting ``expect``
    to raise), and return the non-zero live ledger counts — {} means
    every handle the run created was released."""
    _handles.set_enabled(True)
    _handles.clear()
    try:
        ns = {"rpc": _ledger_rpc_module()}
        src = textwrap.dedent(fixture).replace("import rpc\n", "", 1)
        exec(src, ns)
        if call is not None:
            fn, args = call[0], call[1:]
            if expect is not None:
                with pytest.raises(expect):
                    ns[fn](*args)
            else:
                ns[fn](*args)
        return {k: v for k, v in _handles.live_counts().items() if v}
    finally:
        _handles.set_enabled(None)
        _handles.clear()


_IMPLICIT_THROW_FIXTURE = """\
    import rpc

    def parse(payload):
        if not payload:
            raise ValueError("empty frame")
        return payload

    def leaky(addr, payload):
        ch = rpc.Channel(addr)
        body = parse(payload)
        ch.close()
        return body
"""


def test_implicit_throw_leak_static(tmp_path):
    # the handle leaks ONLY via the callee's raise — no explicit raise,
    # return, or fall-through in sight of the old per-statement pass
    (f,) = _lint_exc_fixture(tmp_path, _IMPLICIT_THROW_FIXTURE)
    assert f.check == "exception-flow"
    assert f.line == 10          # the throwing call, not the binding
    assert "'ch'" in f.message and "ValueError" in f.message
    assert "unwinding edge" in f.message


def test_implicit_throw_leak_dynamic_ledger():
    live = _ledger_run(_IMPLICIT_THROW_FIXTURE,
                       call=("leaky", "addr", b""), expect=ValueError)
    assert live.get("chan") == 1   # the ledger reproduces the leak


_IMPLICIT_THROW_FIXED = """\
    import rpc

    def parse(payload):
        if not payload:
            raise ValueError("empty frame")
        return payload

    def fin(addr, payload):
        ch = rpc.Channel(addr)
        try:
            return parse(payload)
        finally:
            ch.close()

    def ctx(addr, payload):
        with rpc.Channel(addr) as ch:
            return parse(payload)
"""


def test_implicit_throw_finally_and_with_clean_static(tmp_path):
    assert _lint_exc_fixture(tmp_path, _IMPLICIT_THROW_FIXED) == []


def test_implicit_throw_finally_and_with_clean_dynamic():
    for fn in ("fin", "ctx"):
        live = _ledger_run(_IMPLICIT_THROW_FIXED,
                           call=(fn, "addr", b""), expect=ValueError)
        assert live == {}, (fn, live)


_OVERTRUST_FIXTURE = """\
    import rpc

    def parse(payload):
        if not payload:
            raise ValueError("empty frame")
        return payload

    def overtrusting(addr, payload):
        ch = rpc.Channel(addr)
        try:
            size = len(payload)
        except TypeError:
            ch.close()
            raise
        body = parse(payload)
        ch.close()
        return body
"""


def test_handler_trust_scoped_to_its_own_try_static(tmp_path):
    # a release inside SOME handler no longer blesses the whole
    # function: the throwing call sits outside that handler's try
    (f,) = _lint_exc_fixture(tmp_path, _OVERTRUST_FIXTURE)
    assert f.check == "exception-flow"
    assert f.line == 15
    assert "ValueError" in f.message


def test_handler_trust_scoped_dynamic_ledger():
    live = _ledger_run(_OVERTRUST_FIXTURE,
                       call=("overtrusting", "addr", b""),
                       expect=ValueError)
    assert live.get("chan") == 1


def test_handler_covering_call_and_type_clean(tmp_path):
    covered = """\
        import rpc

        def parse(payload):
            if not payload:
                raise ValueError("empty frame")
            return payload

        def covered(addr, payload):
            ch = rpc.Channel(addr)
            try:
                body = parse(payload)
            except ValueError:
                ch.close()
                raise
            ch.close()
            return body
    """
    assert _lint_exc_fixture(tmp_path, covered) == []
    live = _ledger_run(covered, call=("covered", "addr", b""),
                       expect=ValueError)
    assert live == {}


def test_handler_of_wrong_type_does_not_cover(tmp_path):
    (f,) = _lint_exc_fixture(tmp_path, """\
        import rpc

        def parse(payload):
            if not payload:
                raise ValueError("empty frame")
            return payload

        def wrong(addr, payload):
            ch = rpc.Channel(addr)
            try:
                body = parse(payload)
            except OSError:
                ch.close()
                raise
            ch.close()
            return body
    """)
    assert f.check == "exception-flow"
    assert f.line == 11


def test_handler_catches_base_class_of_thrown_type(tmp_path):
    # LookupError covers KeyError through the builtin hierarchy
    assert _lint_exc_fixture(tmp_path, """\
        import rpc

        def pick(table, key):
            return table[key] if key in table else _boom(key)

        def _boom(key):
            raise KeyError(key)

        def covered(addr, table, key):
            ch = rpc.Channel(addr)
            try:
                row = pick(table, key)
            except LookupError:
                ch.close()
                raise
            ch.close()
            return row
    """) == []


_CONTAINER_ESCAPE_FIXTURE = """\
    import rpc

    def burst(addr, n):
        ch = rpc.Channel(addr)
        calls = []
        for _i in range(n):
            calls.append(ch.call_async("Ps", "Apply"))
        ch.close()
"""


def test_container_may_leak_set_static(tmp_path):
    (f,) = _lint_exc_fixture(tmp_path, _CONTAINER_ESCAPE_FIXTURE)
    assert f.check == "handle-lifecycle"
    assert "container 'calls'" in f.message
    assert "never drained" in f.message


def test_container_may_leak_set_dynamic_ledger():
    live = _ledger_run(_CONTAINER_ESCAPE_FIXTURE, call=("burst", "a", 3))
    assert live.get("pending") == 3


_CONTAINER_DRAINED_FIXTURE = """\
    import rpc

    def burst(addr, n):
        ch = rpc.Channel(addr)
        calls = []
        for _i in range(n):
            calls.append(ch.call_async("Ps", "Apply"))
        for pc in calls:
            pc.join()
        ch.close()
"""


def test_container_drained_clean_both_ways(tmp_path):
    assert _lint_exc_fixture(tmp_path, _CONTAINER_DRAINED_FIXTURE) == []
    assert _ledger_run(_CONTAINER_DRAINED_FIXTURE,
                       call=("burst", "a", 3)) == {}


def test_container_returned_or_pragmad_clean(tmp_path):
    returned = _CONTAINER_ESCAPE_FIXTURE.replace(
        "        ch.close()",
        "        ch.close()\n        return calls")
    assert _lint_exc_fixture(tmp_path, returned) == []
    pragmad = _CONTAINER_ESCAPE_FIXTURE.replace(
        'calls.append(ch.call_async("Ps", "Apply"))',
        'calls.append(ch.call_async("Ps", "Apply"))'
        '  # lint: allow-handle-escape')
    assert _lint_exc_fixture(tmp_path, pragmad) == []


_REBIND_FIXTURE = """\
    import rpc

    def reconnect(addr, backup):
        ch = rpc.Channel(addr)
        ch = rpc.Channel(backup)
        ch.close()
"""


def test_rebind_drop_static(tmp_path):
    (f,) = _lint_exc_fixture(tmp_path, _REBIND_FIXTURE)
    assert f.check == "handle-lifecycle"
    assert "rebinding 'ch'" in f.message
    assert f.line == 5


def test_rebind_drop_dynamic_ledger():
    live = _ledger_run(_REBIND_FIXTURE, call=("reconnect", "a", "b"))
    assert live.get("chan") == 1   # the first channel has no name left


def test_rebind_after_release_clean(tmp_path):
    fixed = """\
        import rpc

        def reconnect(addr, backup):
            ch = rpc.Channel(addr)
            ch.close()
            ch = rpc.Channel(backup)
            ch.close()
    """
    assert _lint_exc_fixture(tmp_path, fixed) == []
    assert _ledger_run(fixed, call=("reconnect", "a", "b")) == {}


_MODULE_SCOPE_FIXTURE = """\
    import rpc

    CH = rpc.Channel("127.0.0.1:9999")
"""


def test_module_scope_producer_static(tmp_path):
    (f,) = _lint_exc_fixture(tmp_path, _MODULE_SCOPE_FIXTURE)
    assert f.check == "handle-lifecycle"
    assert "module-scope" in f.message and "'CH'" in f.message


def test_module_scope_producer_dynamic_ledger():
    # the handle is live from import time with no release path
    live = _ledger_run(_MODULE_SCOPE_FIXTURE)
    assert live.get("chan") == 1


def test_module_scope_producer_with_shutdown_clean(tmp_path):
    fixed = _MODULE_SCOPE_FIXTURE + \
        "\n\n    def shutdown():\n        CH.close()\n"
    assert _lint_exc_fixture(tmp_path, fixed) == []
    assert _ledger_run(fixed, call=("shutdown",)) == {}


def test_module_scope_singleton_pragma_accepted(tmp_path):
    pragmad = _MODULE_SCOPE_FIXTURE.replace(
        'CH = rpc.Channel("127.0.0.1:9999")',
        'CH = rpc.Channel("127.0.0.1:9999")  # lint: allow-handle-escape')
    assert _lint_exc_fixture(tmp_path, pragmad) == []


# ---- lock-exception-safety: locks and obligations on unwinding edges ----

_LOCK_MANUAL_FIXTURE = """\
    from brpc_tpu.analysis.race import checked_lock

    MU = checked_lock("lxs.MU")

    def risky(payload):
        if not payload:
            raise ValueError("empty")
        return payload

    def unsafe(payload):
        MU.acquire()
        body = risky(payload)
        MU.release()
        return body
"""


def test_manual_lock_across_throw_static(tmp_path):
    (f,) = _lint_src(tmp_path, _LOCK_MANUAL_FIXTURE,
                     checks=["lock-exception-safety"])
    assert f.check == "lock-exception-safety"
    assert "lxs.MU" in f.message and "may-throw" in f.message
    assert f.line == 12


def test_manual_lock_across_throw_dynamic_parity():
    ns = {"checked_lock": _race.checked_lock}
    exec(textwrap.dedent(_LOCK_MANUAL_FIXTURE).split("\n", 1)[1], ns)
    with pytest.raises(ValueError):
        ns["unsafe"](b"")
    assert ns["MU"].locked()   # left locked forever on the unwind
    ns["MU"].release()


_LOCK_FIXED_FIXTURE = """\
    from brpc_tpu.analysis.race import checked_lock

    MU = checked_lock("lxf.MU")

    def risky(payload):
        if not payload:
            raise ValueError("empty")
        return payload

    def paired(payload):
        MU.acquire()
        try:
            return risky(payload)
        finally:
            MU.release()

    def scoped(payload):
        with MU:
            return risky(payload)
"""


def test_lock_release_in_finally_or_with_clean(tmp_path):
    assert _lint_src(tmp_path, _LOCK_FIXED_FIXTURE,
                     checks=["lock-exception-safety"]) == []
    ns = {"checked_lock": _race.checked_lock}
    exec(textwrap.dedent(_LOCK_FIXED_FIXTURE).split("\n", 1)[1], ns)
    for fn in ("paired", "scoped"):
        with pytest.raises(ValueError):
            ns[fn](b"")
        assert not ns["MU"].locked(), fn


_FENCE_FIXTURE = """\
    class Shard:
        def risky(self, payload):
            if not payload:
                raise ValueError("empty")
            return payload

        def fenced_apply(self, payload):
            self._fencing = True
            body = self.risky(payload)
            self._fencing = False
            return body
"""


def test_fence_flag_half_done_on_unwind_static(tmp_path):
    (f,) = _lint_src(tmp_path, _FENCE_FIXTURE,
                     checks=["lock-exception-safety"])
    assert f.check == "lock-exception-safety"
    assert "_fencing" in f.message and "finally" in f.message
    assert f.line == 9


def test_fence_flag_half_done_on_unwind_dynamic():
    ns = {}
    exec(textwrap.dedent(_FENCE_FIXTURE), ns)
    sh = ns["Shard"]()
    with pytest.raises(ValueError):
        sh.fenced_apply(b"")
    assert sh._fencing is True   # the half-done obligation, observable


def test_fence_flag_reset_in_finally_clean(tmp_path):
    fixed = """\
        class Shard:
            def risky(self, payload):
                if not payload:
                    raise ValueError("empty")
                return payload

            def fenced_apply(self, payload):
                self._fencing = True
                try:
                    return self.risky(payload)
                finally:
                    self._fencing = False
    """
    assert _lint_src(tmp_path, fixed,
                     checks=["lock-exception-safety"]) == []
    ns = {}
    exec(textwrap.dedent(fixed), ns)
    sh = ns["Shard"]()
    with pytest.raises(ValueError):
        sh.fenced_apply(b"")
    assert sh._fencing is False


# ---- lock-order: class containers inherited from base classes ----

_INHERITED_CONTAINER_LOCK_FIXTURE = """\
    from brpc_tpu.analysis.race import checked_lock

    class Base:
        LOCKS = {"a": checked_lock("mro.A"), "b": checked_lock("mro.B")}

    class Engine(Base):
        def fwd(self):
            with self.LOCKS["a"]:
                with self.LOCKS["b"]:
                    pass

        def rev(self):
            with self.LOCKS["b"]:
                with self.LOCKS["a"]:
                    pass
"""


def test_inherited_class_container_lock_resolves(tmp_path):
    # the container lives on Base; the inversion is in the subclass —
    # the base-chain walk binds self.LOCKS["a"] through the MRO
    fs = _lint_src(tmp_path, _INHERITED_CONTAINER_LOCK_FIXTURE)
    (f,) = _by_check(fs, "lock-order")
    assert "mro.A" in f.message and "mro.B" in f.message


def test_inherited_container_lock_matches_dynamic_harness(tmp_path):
    static = _by_check(
        _lint_src(tmp_path, _INHERITED_CONTAINER_LOCK_FIXTURE),
        "lock-order")
    assert len(static) == 1

    _race.clear()
    _race.set_enabled(True)
    try:
        ns = {"checked_lock": _race.checked_lock}
        exec(textwrap.dedent(
            _INHERITED_CONTAINER_LOCK_FIXTURE).split("\n", 1)[1], ns)
        eng = ns["Engine"]()
        eng.fwd()
        eng.rev()
        dynamic = [f for f in _race.findings()
                   if f.kind == "lock-inversion"]
    finally:
        _race.set_enabled(None)
        _race.clear()
    assert len(dynamic) == 1
    assert {"mro.A", "mro.B"} <= set(dynamic[0].locks)


def test_inherited_container_shadowed_nonliteral_stays_deferred(tmp_path):
    shadowed = _INHERITED_CONTAINER_LOCK_FIXTURE.replace(
        "class Engine(Base):",
        "class Engine(Base):\n    LOCKS = dict(Base.LOCKS)")
    assert _by_check(_lint_src(tmp_path, shadowed), "lock-order") == []


def test_inherited_container_mutated_in_subclass_stays_deferred(tmp_path):
    mutated = _INHERITED_CONTAINER_LOCK_FIXTURE.replace(
        "    def fwd(self):",
        "    def grow(self):\n"
        "        self.LOCKS[\"c\"] = checked_lock(\"mro.C\")\n"
        "\n"
        "    def fwd(self):")
    assert _by_check(_lint_src(tmp_path, mutated), "lock-order") == []
