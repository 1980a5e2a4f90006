"""Inference API (reference paddle/fluid/inference/: AnalysisPredictor
`api/analysis_predictor.h:82`, AnalysisConfig `api/paddle_analysis_config.h`,
C API `inference/capi/`).

TPU-native: the saved "model" is a serialized jax.export program
(StableHLO) + params — the analysis pass pipeline (fusion, memory
optimization, layout) is XLA's job at AOT-compile time, so Config's
switches map to compile options instead of IR pass lists. The Predictor
surface (named input/output handles, copy_from_cpu/run/copy_to_cpu)
mirrors the reference's zero-copy API.

Two serving surfaces live behind this frontend:
- per-call artifacts: ``create_predictor(Config(...))`` below — one
  exported program, dense inputs, the reference's deployment shape;
- LM request streams: ``create_serving_engine(model, ...)`` — the
  continuous-batching engine (paddle_tpu.serving: paged KV cache,
  bucketed prefill, in-flight admission) for mixed-length traffic
  that a per-call Predictor would serialize behind head-of-line
  batches and per-signature recompiles.
"""
from __future__ import annotations

import json
import os
import pickle
from typing import Dict, List, Optional

import numpy as np

__all__ = ["Config", "Predictor", "Tensor", "create_predictor",
           "create_serving_engine"]


class Config:
    """AnalysisConfig analogue: points at the exported artifact."""

    def __init__(self, model_path: Optional[str] = None,
                 params_path: Optional[str] = None):
        if model_path and model_path.endswith(".pdmodel"):
            model_path = model_path[:-len(".pdmodel")]
        self._prefix = model_path
        self._device = None  # default: jax's default backend
        self._memory_pool_mb = 0
        self._ir_optim = True  # parity flag: XLA always optimizes

    # -- device selection (CUDA/XPU knobs kept for API parity) -------------
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._memory_pool_mb = memory_pool_init_size_mb
        self._device = ("tpu", device_id)  # GPU request maps to the chip

    def enable_tpu(self, device_id=0):
        self._device = ("tpu", device_id)

    def disable_gpu(self):
        self._device = ("cpu", 0)

    def use_gpu(self):
        return self._device is not None and self._device[0] != "cpu"

    def switch_ir_optim(self, flag=True):
        self._ir_optim = flag

    def model_dir(self):
        return os.path.dirname(self._prefix or "")

    def prog_file(self):
        return (self._prefix or "") + ".pdmodel"

    def params_file(self):
        return (self._prefix or "") + ".pdiparams"


class Tensor:
    """ZeroCopyTensor analogue: a named input/output slot."""

    def __init__(self, predictor: "Predictor", name: str, is_input: bool):
        self._p = predictor
        self.name = name
        self._is_input = is_input

    def reshape(self, shape):
        pass  # shapes come from the exported program; kept for API parity

    def copy_from_cpu(self, data: np.ndarray):
        if not self._is_input:
            raise RuntimeError(f"{self.name} is an output handle")
        self._p._feeds[self.name] = np.asarray(data)

    def copy_to_cpu(self) -> np.ndarray:
        if self._is_input:
            raise RuntimeError(f"{self.name} is an input handle")
        return np.asarray(self._p._outputs[self.name])

    def shape(self):
        if self._is_input:
            a = self._p._feeds.get(self.name)
            return list(a.shape) if a is not None else None
        return list(np.shape(self._p._outputs[self.name]))


class Predictor:
    """AnalysisPredictor analogue: deserialize program + params, AOT-run."""

    def __init__(self, config: Config):
        from jax import export as jax_export
        self.config = config
        if config._device is not None and config._device[0] == "cpu":
            # disable_gpu() must actually pin the CPU backend. The
            # update is a silent no-op once any backend has initialized,
            # so verify and fail LOUDLY rather than run elsewhere.
            import jax
            jax.config.update("jax_platforms", "cpu")
            backend = jax.default_backend()
            if backend != "cpu":
                raise RuntimeError(
                    f"Config.disable_gpu(): jax already initialized the "
                    f"'{backend}' backend in this process — construct "
                    "the Predictor before any other jax use, or set "
                    "JAX_PLATFORMS=cpu in the environment")
        prefix = config._prefix
        with open(prefix + ".pdmodel", "rb") as f:
            self._exported = jax_export.deserialize(f.read())
        with open(prefix + ".pdiparams", "rb") as f:
            data = pickle.load(f)
        self._state = {k: np.asarray(v) for k, v in data["state"].items()}
        meta_path = prefix + ".pdmeta.json"
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                meta = json.load(f)
            self._input_names = meta.get("feed_names") or []
            self._output_names = meta.get("fetch_names") or []
        else:
            spec = data.get("meta", {}).get("input_spec") or []
            self._input_names = [f"x{i}" for i in range(len(spec))]
            self._output_names = []
        if not self._input_names:
            # exported in_avals: state tree leaves first, then inputs
            n_state = len(self._state)
            n_in = len(self._exported.in_avals) - n_state
            self._input_names = [f"x{i}" for i in range(n_in)]
        self._feeds: Dict[str, np.ndarray] = {}
        self._outputs: Dict[str, np.ndarray] = {}

    def get_input_names(self) -> List[str]:
        return list(self._input_names)

    def get_output_names(self) -> List[str]:
        if self._output_names:
            return list(self._output_names)
        return [f"out{i}" for i in range(len(self._exported.out_avals))]

    def get_input_handle(self, name: str) -> Tensor:
        return Tensor(self, name, is_input=True)

    def get_output_handle(self, name: str) -> Tensor:
        return Tensor(self, name, is_input=False)

    def run(self, inputs: Optional[List[np.ndarray]] = None):
        if inputs is not None:
            for n, a in zip(self._input_names, inputs):
                self._feeds[n] = np.asarray(a)
        args = [self._feeds[n] for n in self._input_names]
        out = self._exported.call(self._state, *args)
        flat = out if isinstance(out, (list, tuple)) else [out]
        names = self.get_output_names()
        self._outputs = {n: np.asarray(a) for n, a in zip(names, flat)}
        if inputs is not None:
            return [self._outputs[n] for n in names]


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


def create_serving_engine(model, serving_config=None, warmup=True,
                          plan=None, **config_kw):
    """The serving twin of create_predictor: build a warmed
    continuous-batching ServingEngine over a live GPTForCausalLM.
    Keyword overrides construct a paddle_tpu.serving.ServingConfig
    (e.g. ``max_slots=16, dtype=None``); ``warmup=False`` skips the
    ladder compile (tests that only inspect structure).

    ``plan=MeshPlan(tp=N)`` builds the tensor-parallel engine: ONE
    shard_map program set over the tp axis with the paged K/V pools
    sharded over heads — tp must divide the model's head count
    (validated at config time, the error names both dims)."""
    from ..serving import ServingConfig, ServingEngine
    if serving_config is not None and (config_kw or plan is not None):
        raise ValueError(
            "pass either serving_config or keyword overrides, not both")
    cfg = serving_config or ServingConfig(plan=plan, **config_kw)
    eng = ServingEngine(model, cfg)
    return eng.warmup() if warmup else eng
