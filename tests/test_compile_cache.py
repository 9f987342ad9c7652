"""The persistent compilation cache rule (repro.launch.compile_cache): with
JAX_COMPILATION_CACHE_DIR set the cache lives only there; otherwise at the
fixed <checkout>/.jax_cache.  Each case runs in a fresh interpreter, since
JAX's cache configuration is process-global.

Other tests may write to the checkout cache at the same time (the CLIs call
`enable_compile_cache`), so each case compiles a function with a name of its
own and looks only for that function's cache entry."""
import os
import subprocess
import sys
import uuid
from pathlib import Path

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR

REPO = Path(__file__).resolve().parents[1]

_PROGRAM = """
import sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
def probe(x):
    return jnp.sin(x) * 3.0 + 1.0
probe.__name__ = sys.argv[1]
jax.jit(probe)(jnp.arange(7.0)).block_until_ready()
"""


_SCOPED = """
import sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
enable_compile_cache()
def probe(x):
    with jax.named_scope(sys.argv[2]):
        return jnp.sin(x) * 3.0 + 1.0
probe.__name__ = sys.argv[1]
jax.jit(probe)(jnp.arange(7.0)).block_until_ready()
print(0, 0)
"""


def _run(env_extra, drop=(), name=None, program=_PROGRAM, args=()):
    """Compile one uniquely named function in a fresh interpreter; return
    (cache dir returned, cache dir configured, function name)."""
    name = name or f"cache_probe_{uuid.uuid4().hex}"
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0", **env_extra)
    r = subprocess.run([sys.executable, "-c", program, name, *args],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    returned, configured = r.stdout.split()
    return returned, configured, name


def _entries(d: Path, name: str):
    """Cache entries of the jitted function `name` under `d`."""
    return sorted(d.glob(f"jit_{name}-*")) if d.exists() else []


def test_env_dir_is_the_only_cache(tmp_path):
    cache = tmp_path / "cache"
    returned, configured, name = _run(
        {"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert returned == configured == str(cache)
    assert _entries(cache, name), "the compile was not cached in the env dir"
    assert not _entries(CHECKOUT_CACHE_DIR, name)


def test_default_is_fixed_checkout_dir():
    returned, configured, name = _run({}, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert returned == configured == str(REPO / ".jax_cache")
    mine = _entries(CHECKOUT_CACHE_DIR, name)
    try:
        assert mine, "the compile was not cached in <checkout>/.jax_cache"
    finally:
        for p in mine:
            p.unlink()


def test_named_scopes_are_part_of_the_key(tmp_path):
    """The same program twice shares one entry; under another named scope
    it gets its own, so a profiler trace shows the scopes of the build that
    ran, never those of a build whose executable was cached."""
    cache = tmp_path / "cache"
    name = f"cache_probe_{uuid.uuid4().hex}"
    for scope in ("stem", "stem", "head"):
        _run({"JAX_COMPILATION_CACHE_DIR": str(cache)}, name=name,
             program=_SCOPED, args=(scope,))
    assert len(_entries(cache, name)) == 2
