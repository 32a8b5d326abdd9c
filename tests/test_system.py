"""End-to-end system tests: the real drivers, run as a user would."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra=None, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, env=env,
                          capture_output=True, text=True, timeout=timeout,
                          cwd=REPO)


@pytest.mark.slow
def test_solver_driver_end_to_end_checked():
    """The paper's workload: solve, verify against the reference sweep."""
    p = _run(["-m", "repro.launch.solve", "--ny", "64", "--nx", "128",
              "--iters", "50", "--kernel", "v1", "--check"])
    assert p.returncode == 0, p.stderr
    assert "CHECK OK" in p.stdout


@pytest.mark.slow
def test_solver_distributed_driver():
    p = _run(["-m", "repro.launch.solve", "--ny", "64", "--nx", "128",
              "--iters", "48", "--devices", "4", "--depth", "8",
              "--check"],
             env_extra={"XLA_FLAGS":
                        "--xla_force_host_platform_device_count=4"})
    assert p.returncode == 0, p.stderr
    assert "CHECK OK" in p.stdout


@pytest.mark.slow
def test_train_driver_losses_drop_and_resume(tmp_path):
    """Train 14 steps, kill, resume from checkpoint, finish to 20."""
    ck = str(tmp_path / "ck")
    p = _run(["-m", "repro.launch.train", "--arch", "qwen2.5-3b", "--smoke",
              "--steps", "14", "--batch", "4", "--seq", "64",
              "--ckpt-dir", ck, "--ckpt-every", "5"])
    assert p.returncode == 0, p.stderr
    first = [ln for ln in p.stdout.splitlines() if "first ce" in ln][0]
    l0, l1 = (float(x.split("=")[1]) for x in first.split(";")[1].split()
              if "=" in x)
    assert l1 < l0, first

    p2 = _run(["-m", "repro.launch.train", "--arch", "qwen2.5-3b", "--smoke",
               "--steps", "20", "--batch", "4", "--seq", "64",
               "--ckpt-dir", ck, "--resume", "auto"])
    assert p2.returncode == 0, p2.stderr
    assert "resumed from step" in p2.stdout


@pytest.mark.slow
def test_serve_driver():
    p = _run(["-m", "repro.launch.serve", "--arch", "mamba2-2.7b", "--smoke",
              "--requests", "4", "--batch", "2", "--prompt-len", "8",
              "--max-new", "6"])
    assert p.returncode == 0, p.stderr
    assert "tok/s=" in p.stdout


def test_compile_cache_dir_comes_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache sits
    at one fixed, git-ignored path inside the checkout."""
    import jax

    from repro import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        assert path == os.path.join(compile_cache.REPO_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        ignored = open(os.path.join(compile_cache.REPO_ROOT,
                                    ".gitignore")).read().split()
        assert ".jax_cache/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
