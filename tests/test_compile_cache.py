"""The persistent compilation cache has one helper and two rules
(core.flags.apply_compile_cache): where JAX_COMPILATION_CACHE_DIR is
set jax already has the directory and the program updates nothing;
otherwise the cache is <checkout>/.jax_cache. The sentinel's
jax.monitoring listener counts cache requests and hits on their own
meters, so a cache HIT is an observable receipt, not an inference from
wall time."""
import os
import pathlib
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.core import flags as pd_flags
from paddle_tpu.observability import metrics, sentinel

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_config():
    """The cache config is process-global: put it back so the rest of
    the suite doesn't write every tiny compile to disk."""
    from jax._src import compilation_cache as _cc
    prev = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    _cc.reset_cache()   # drop the latched file-cache object too


def test_env_placed_cache_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: updates.append(a))
    assert pd_flags.apply_compile_cache() == str(tmp_path)
    assert updates == []


def test_default_cache_is_the_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert pd_flags.apply_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_only_the_helper_places_the_cache():
    """No entry point, tool or module sets the cache directory itself
    (or carries one of the retired spellings)."""
    # the retired name is spelled in two halves: a repo-wide grep for
    # it must stay empty, this file included
    pat = re.compile("jax_compilation_cache_dir|PD_COMPILE" "_CACHE_DIR"
                     "|FLAGS_compile_cache_dir")
    hits = []
    for top in ("paddle_tpu", "tools", "examples", "bench.py",
                "chip_smoke.py", "__graft_entry__.py"):
        root = REPO / top
        for f in ([root] if root.is_file() else sorted(root.rglob("*.py"))):
            if f != REPO / "paddle_tpu" / "core" / "flags.py" \
                    and pat.search(f.read_text()):
                hits.append(str(f.relative_to(REPO)))
    assert hits == []


def test_compile_cache_hits_observable(tmp_path, restore_cache_config):
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path / "xla_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from jax._src import compilation_cache as _cc
    _cc.reset_cache()   # earlier tests compiled: un-latch "no cache"

    assert sentinel.attach_jax_compile_hook()
    req = metrics.counter("jax.compile_cache.requests", _always=True)
    hits = metrics.counter("jax.compile_cache.hits", _always=True)
    req0, hit0 = req.value(), hits.value()

    x = jnp.asarray(np.arange(64, dtype=np.float32).reshape(8, 8))
    # two DISTINCT jit objects over an identical computation: the
    # second lowers the same HLO, misses the in-process executable
    # cache, and must be served from the persistent cache on disk
    f1 = jax.jit(lambda a: jnp.tanh(a @ a.T).sum(axis=0) * 3.0)
    f2 = jax.jit(lambda a: jnp.tanh(a @ a.T).sum(axis=0) * 3.0)
    r1 = np.asarray(f1(x))
    r2 = np.asarray(f2(x))
    np.testing.assert_allclose(r1, r2)
    assert req.value() >= req0 + 2
    assert hits.value() >= hit0 + 1, (
        "second identical program did not hit the persistent cache")
    assert os.listdir(tmp_path / "xla_cache")
