"""The one persistent compile cache, placed from outside
(gofr_tpu.utils.enable_compilation_cache):

- JAX_COMPILATION_CACHE_DIR set -> that directory and no other;
- unset -> ``<checkout>/.xla_cache``, never the home directory;
- in both cases small, fast-compiling programs are persisted (thresholds
  lowered) and the helper takes effect even when jax has already compiled
  something in the process: jax initializes its persistent-cache object on
  the FIRST compile and ignores later config updates, so without the reset
  an app that does any jax work before engine init would silently lose the
  cache for the whole process.

Runs in subprocesses: the state is per-process, and the suite's own
conftest has already configured this process's cache.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROG = """
import os
import jax, jax.numpy as jnp
# something compiles BEFORE the cache is configured (the reset's reason)
jax.jit(lambda x: x + 1)(jnp.ones(4)).block_until_ready()
from gofr_tpu.utils import enable_compilation_cache
print(enable_compilation_cache())
print(jax.config.jax_compilation_cache_dir)
# a sub-second program: persisted only with the thresholds lowered
jax.jit(lambda x: (x @ x.T).mean())(jnp.ones((32, 32))).block_until_ready()
"""


def _run(env_overrides: dict, cwd: str) -> list[str]:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO, **env_overrides}
    env = {k: v for k, v in env.items() if v is not None}  # None unsets
    out = subprocess.run(
        [sys.executable, "-c", _PROG], env=env, capture_output=True,
        text=True, timeout=120, cwd=cwd,
    )
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()


def test_env_set_uses_that_directory_and_no_other(tmp_path):
    cache_dir = tmp_path / "placed" / "from" / "outside"
    home = tmp_path / "home"
    home.mkdir()
    returned, configured = _run(
        {"JAX_COMPILATION_CACHE_DIR": str(cache_dir), "HOME": str(home)},
        cwd=str(tmp_path),
    )
    assert returned == configured == str(cache_dir)
    assert os.listdir(cache_dir), (
        "no entries: a small program compiled after a prior compile was not "
        "persisted in the directory JAX_COMPILATION_CACHE_DIR names"
    )
    assert os.listdir(home) == []  # nothing under ~


def test_env_unset_uses_the_checkout(tmp_path):
    """<checkout>/.xla_cache whatever the working directory; a copy of the
    package stands in for the checkout so the real one stays untouched."""
    checkout = tmp_path / "checkout"
    pkg = checkout / "gofr_tpu"
    (pkg / "utils").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    src = os.path.join(REPO, "gofr_tpu", "utils", "__init__.py")
    (pkg / "utils" / "__init__.py").write_text(open(src).read())
    home = tmp_path / "home"
    home.mkdir()
    returned, configured = _run(
        {"JAX_COMPILATION_CACHE_DIR": None, "HOME": str(home),
         "PYTHONPATH": str(checkout)},
        cwd=str(tmp_path),
    )
    assert returned == configured == str(checkout / ".xla_cache")
    assert os.listdir(checkout / ".xla_cache")
    assert os.listdir(home) == []


def test_a_platform_that_did_not_take_is_an_error():
    """TPU_PLATFORM names a backend; jax already initialized on another
    one (this suite's CPU) must raise, not warn and serve there."""
    import jax
    import pytest

    from gofr_tpu.utils import pin_jax_platform

    pin_jax_platform("")  # unset: nothing to pin
    pin_jax_platform("cpu")  # the active backend: takes
    try:
        with pytest.raises(RuntimeError, match="did not take"):
            pin_jax_platform("tpu")
    finally:
        jax.config.update("jax_platforms", "cpu")  # the suite's own choice
