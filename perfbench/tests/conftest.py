"""The benchmark's own tests run on the CPU."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
