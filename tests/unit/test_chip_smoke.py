"""chip_smoke.py's contract off the chip, and the compile-cache placement rule."""

import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_chip_smoke_fails_without_a_chip():
    """On the CPU the smoke exits non-zero before any phase and never prints
    the success line (a measurement path that finds no chip fails)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert '"phase"' not in proc.stdout  # no phase ran
    assert "no TPU" in proc.stderr


def test_the_rehearsal_drives_the_trainer_phase_to_its_end():
    """``--rehearse --only trainer`` lowers the engine's ``fused_step`` with the smoke's own arguments and takes its
    steps: a change of that program's signature (PR 56: the compute copy beside the master) fails here and not first
    on the chip."""
    import json

    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py"), "--rehearse", "--only", "trainer"],
                          cwd=REPO, env=dict(os.environ), capture_output=True, text=True, timeout=600)
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.startswith("{")]
    phase = [l for l in lines if l.get("phase") == "trainer"]
    assert proc.returncode == 0 and len(phase) == 1 and phase[0]["ok"], (proc.stdout[-2000:], proc.stderr[-2000:])
    assert lines[-1] == {"ok": False, "rehearsal": "passed", "device": lines[-1]["device"]}  # never the chip's ok line


@pytest.mark.parametrize("env_dir", [None, "/some/where/placed/from/outside"])
def test_compile_cache_dir_is_placed_from_outside(env_dir, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the one fixed
    <checkout>/.jax_cache_tpu — never a temporary name, a pid or a time."""
    from deepspeed_tpu.utils import compile_cache

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    monkeypatch.delenv("DS_BENCH_NO_CACHE", raising=False)
    updates = {}
    fake_jax = types.SimpleNamespace(config=types.SimpleNamespace(update=updates.__setitem__))
    used = compile_cache.enable_compilation_cache(fake_jax)
    want = env_dir or os.path.join(REPO, ".jax_cache_tpu")
    assert used == want == updates["jax_compilation_cache_dir"]
    assert compile_cache.CHECKOUT_CACHE_DIR == os.path.join(REPO, ".jax_cache_tpu")
