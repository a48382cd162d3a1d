"""Launch plumbing: where the persistent compilation cache goes, and that
the training supervisor's parent never starts a JAX backend (subprocesses:
both are process-wide JAX state)."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(code: str, **env_extra) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(ROOT, "src"), **env_extra)
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout.strip().splitlines()[-1]


CACHE_DIR = """
    import jax
    from repro.utils.compile_cache import enable_compile_cache
    print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_defaults_to_the_checkout():
    returned, configured = _run(CACHE_DIR).split()
    assert returned == configured == os.path.join(ROOT, ".jax_cache")


def test_compile_cache_honours_the_environment(tmp_path):
    where = str(tmp_path / "cache")
    returned, configured = _run(CACHE_DIR,
                                JAX_COMPILATION_CACHE_DIR=where).split()
    assert returned == configured == where


def test_supervisor_parent_starts_no_backend():
    """``train --supervise`` only launches children; the child that trains
    must find the chip free, so the parent may import JAX but never start a
    backend."""
    out = _run("""
        from jax._src import xla_bridge
        import repro.launch.train as train

        class Done:
            returncode = 0

        train.subprocess.run = lambda *a, **k: Done()
        assert train.supervise(["--steps", "1"]) == 0
        print("backends", len(xla_bridge._backends))
    """)
    assert out == "backends 0"
