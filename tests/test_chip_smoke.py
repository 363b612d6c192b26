"""chip_smoke.py off the chip: its explicit CPU dry run works end to end and
says it ran on the CPU; its default invocation refuses a CPU-only
environment at once instead of running there. Plus the compile-cache helper
every jitting entry point calls. The chip run itself is made through the
chip tool (see .claude/skills/verify/SKILL.md)."""

import json
import os
import subprocess
import sys
import time

import jax
import pytest

from brpc_tpu import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(*args, env=None, timeout=600):
    return subprocess.run([sys.executable, SMOKE, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.needs_native
def test_cpu_dry_run_covers_every_phase_and_says_cpu():
    proc = _run("--cpu-dry-run", "--chips", "4")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    # The last line is the verdict, with exactly these keys (the driver's
    # contract); the summary of what ran is the line before it.
    verdict, summary = lines[-1], lines[-2]
    assert verdict == {"ok": True, "device": {
        "platform": "cpu", "kind": verdict["device"]["kind"], "count": 4}}
    assert isinstance(verdict["device"]["kind"], str)
    assert list(summary) == ["phases", "claim"] and summary["claim"] is None
    assert summary["phases"] == ["kernel", "train", "ps", "multichip_jax",
                                 "multichip_native"]
    phases = {ln["phase"]: ln for ln in lines[:-2]}
    assert set(phases) == set(summary["phases"])
    for name, line in phases.items():
        assert line["platform"] == "cpu", name
        assert line["device_kind"] and line["device_count"] >= 1, name
        assert "run_s" in line["seconds"] or name == "multichip_jax", name
    kernel = phases["kernel"]
    assert kernel["interpret"] is True
    # Output and all three gradients were compared, each within the
    # tolerance of its scale, and both forms were timed.
    assert set(kernel["max_err_over_scale"]) == {"o", "dq", "dk", "dv"}
    assert all(e <= kernel["tolerance"]
               for e in kernel["max_err_over_scale"].values())
    assert set(kernel["forward_backward_ms"]) == {"kernel", "dense"}
    assert phases["ps"]["pjrt_platform"] == "brt_fake"
    assert phases["ps"]["leaked_handles"] == 0
    assert phases["multichip_native"]["launched_on"] == [0, 1, 2, 3]


def test_default_run_refuses_a_cpu_only_environment_at_once():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = _run(env=env, timeout=60)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""          # no result line
    assert "JAX_PLATFORMS=cpu" in proc.stderr
    # At once: no child started, so nothing loaded libtpu (which without a
    # chip retries for minutes) and nothing ran on the CPU instead.
    assert time.monotonic() - t0 < 10


def test_compile_cache_leaves_the_environments_directory_alone(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_is_one_fixed_ignored_directory(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.enable() is None     # the CPU backend: no cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        assert compile_cache.enable() == compile_cache.DEFAULT_DIR
        assert (jax.config.jax_compilation_cache_dir
                == os.path.join(ROOT, ".jax_cache"))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(ROOT, ".gitignore"), encoding="utf-8") as f:
        assert ".jax_cache/" in f.read().split()
