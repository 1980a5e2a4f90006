"""Persistent compile-cache hits of this process up to the opening of
the window (jax.compile_cache.hits): a warm run finds every program."""


def read(ctx):
    return ctx["bench"]["compile_cache_hits"]
