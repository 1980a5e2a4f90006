"""Force the CPU XLA backend with N virtual devices — the ONE copy.

graph_lint, memory_anatomy and memory_receipts all need the same
dance, and before this module each carried a drifting hand-rolled
variant (tests/conftest.py keeps its own: it must run as a pytest
plugin before any tool imports). The dance: act BEFORE the jax
backend initializes.
"""
import os

__all__ = ["force_cpu_devices"]


def force_cpu_devices(n: int, strict: bool = False):
    """Returns the jax module with the CPU backend forced to >= ``n``
    virtual devices. ``strict=True`` asserts the count (the receipts
    contract: a silently wrong mesh voids the receipt); the default
    tolerates an already-initialized backend (pytest's conftest
    forced 8, the lint tools use what's there).
    """
    import jax
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        pass  # backend already up: use what is there
    if strict:
        assert len(jax.devices()) >= n
    return jax
