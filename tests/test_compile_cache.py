"""`enable_compile_cache()` keys the persistent compilation cache on a
program's op names, scopes included, and not on where its source lives: the
same program traced from another file or line hits, a renamed
`jax.named_scope` misses. `repro.serving.compiles` counts the compiles and
the cache hits, and `chip_smoke.py` reads its compile line from it."""
import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.compile_cache import enable_compile_cache
from repro.serving import compiles

OPTIONS = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
           "jax_compilation_cache_include_metadata_in_key",
           "jax_traceback_in_locations_limit",
           "jax_persistent_cache_min_compile_time_secs",
           "jax_persistent_cache_min_entry_size_bytes")

SOURCE = '''
def scoped(x):
    with jax.named_scope("{scope}"):
        y = jnp.sin(x) * 2
    return y + 1
'''

BASE = ("/checkout_a/src/prog.py", "attention", 0)


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """The cache as `enable_compile_cache()` sets it, in `tmp_path`; every
    option it or this test touches is put back afterwards."""
    saved = {k: getattr(jax.config, k) for k in OPTIONS}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    compilation_cache.reset_cache()
    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        yield tmp_path
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def _scoped(filename: str, scope: str, blank_lines: int):
    """`scoped`, traced from source that claims to live in `filename`,
    `blank_lines` lines down."""
    code = compile("\n" * blank_lines + SOURCE.format(scope=scope),
                   filename, "exec")
    ns = {"jax": jax, "jnp": jnp}
    exec(code, ns)
    return ns["scoped"]


def _compile(filename: str, scope: str, blank_lines: int) -> str:
    fn = _scoped(filename, scope, blank_lines)
    return jax.jit(fn).lower(np.ones(8, np.float32)).compile().as_text()


def _keys(path) -> set:
    return {name.rsplit("-", 1)[0] if name.endswith(("-cache", "-atime"))
            else name for name in os.listdir(path)
            if name.startswith("jit_scoped")}


@pytest.mark.parametrize("other,entries", [
    (("/elsewhere/checkout_b/src/prog.py", "attention", 0), 1),
    (("/checkout_a/src/prog.py", "attention", 7), 1),
    (("/checkout_a/src/prog.py", "serve_matmul", 0), 2),
], ids=["moved_checkout", "moved_lines", "renamed_scope"])
def test_cache_key_follows_scopes_not_source_locations(cache_dir, other,
                                                       entries):
    _compile(*BASE)
    assert len(_keys(cache_dir)) == 1
    _compile(*other)
    assert len(_keys(cache_dir)) == entries


def test_programs_carry_no_source_location(cache_dir):
    """Op metadata keeps the scope but names no source file or line."""
    text = _compile(*BASE)
    assert 'op_name="jit(scoped)/attention/' in text
    assert "source_file" not in text and "prog.py" not in text


def test_compile_counter_counts_cache_hits(cache_dir):
    """A program fetched from the persistent cache counts as a compile and
    as a cache hit; a fresh one as a compile only."""
    before = compiles.snapshot()
    _compile(*BASE)
    fresh = compiles.snapshot()
    assert fresh["compiles"] == before["compiles"] + 1
    assert fresh["cache_hits"] == before["cache_hits"]
    jax.clear_caches()
    _compile(*BASE)
    hit = compiles.snapshot()
    assert hit["compiles"] == fresh["compiles"] + 1
    assert hit["cache_hits"] == fresh["cache_hits"] + 1


def test_chip_smoke_reads_the_compile_counter():
    """`chip_smoke.compile_seconds` reports the counter's compiles inside
    its block, and none from outside it."""
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    x = np.ones(5, np.float32)
    jax.jit(lambda x: x - 5)(x).block_until_ready()
    with smoke.compile_seconds() as out:
        jax.jit(lambda x: x * 7 + 2)(x).block_until_ready()
        jax.jit(lambda x: x / 3 - 1)(x).block_until_ready()
    assert out["compiles"] == 2
    assert out["seconds"] > 0
    assert out["cache_hits"] == 0
