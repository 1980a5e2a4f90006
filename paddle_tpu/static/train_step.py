"""TrainStep: whole-step compilation (the TPU performance path).

The reference runs training as a per-op interpreter loop
(executor.cc:461 / dygraph tracer) — on TPU that would leave the MXU idle
between dispatches. Here the entire step (forward + loss + backward +
optimizer update + LR schedule + loss scaling) compiles to ONE XLA
executable via jax.jit, with parameters/optimizer state as donated pytree
inputs so updates happen in-place in HBM.

Sharding: pass a Mesh + a ShardingPlan (paddle_tpu.distributed) and every
pytree leaf gets a NamedSharding — XLA inserts the collectives (DP grad
all-reduce ≡ reference's c_allreduce_sum graph rewrite, ZeRO state
sharding ≡ sharding_optimizer.py — but as compiler-placed reduce-scatter/
all-gather over ICI instead of graph surgery).
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.generator import key_scope, next_key
from ..framework import Tensor, no_grad
from ..jit.api import _unwrap_tree, _wrap_tree
from ..nn.layer.layers import Layer
from ..observability import flight_recorder as _fr
from ..observability import memory as _mem
from ..observability import metrics as _obs
from ..observability.anatomy import scope as _scope
from ..observability.sentinel import RecompileSentinel, signature_of
from ..optimizer.optimizer import Optimizer
from ..optimizer.lr import LRScheduler

__all__ = ["TrainStep"]


def _microslice(a, idx, accum):
    """Slice microbatch idx of `accum` along the batch dim."""
    if jnp.ndim(a) == 0:
        return a
    micro = a.shape[0] // accum
    return jax.lax.dynamic_slice_in_dim(a, idx * micro, micro, axis=0)


class TrainStep:
    """Compiled training step.

    loss_fn(outputs, *labels) -> scalar Tensor, written in paddle ops.
    Usage:
        step = TrainStep(model, loss_fn, optimizer)
        loss = step(inputs, labels)   # one fused XLA step
    """

    def __init__(self, layer: Layer, loss_fn: Callable,
                 optimizer: Optimizer, amp_level: Optional[str] = None,
                 amp_dtype="bfloat16", mesh=None, sharding_plan=None,
                 donate: bool = True, grad_accum_steps: int = 1,
                 grad_transform: Optional[Callable] = None,
                 strategy_state: Optional[Dict[str, Any]] = None,
                 remat: bool = False, remat_policy=None, scaler=None,
                 sentry=None):
        self.layer = layer
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.amp_level = amp_level
        self.amp_dtype = amp_dtype
        # In-graph dynamic loss scaling (reference
        # operators/amp/{check_finite_and_unscale,update_loss_scaling}
        # ops): pass an amp.GradScaler/AmpScaler and its state lives in
        # strategy_state as traced scalars — scale/unscale, the finite
        # check, the skip-step select, and the scale update all compile
        # into the step; no host sync (unlike GradScaler.step eager-side).
        self._scaler_cfg = None
        if scaler is not None and getattr(scaler, "_enable", True):
            self._scaler_cfg = {
                "init_scale": float(scaler._scale),
                "incr_ratio": float(scaler._incr_ratio),
                "decr_ratio": float(scaler._decr_ratio),
                "incr_every_n": int(scaler._incr_every_n),
                "decr_every_n": int(scaler._decr_every_n),
                "dynamic": bool(scaler._dynamic),
            }
        self.mesh = mesh
        self.sharding_plan = sharding_plan
        self.grad_accum_steps = grad_accum_steps
        # fleet meta-optimizer hooks: grad_transform(grads, strat_state,
        # params) -> (grads, strat_state) runs between backward and the
        # optimizer update (DGC / fp16-allreduce analogues); remat wraps
        # the forward in jax.checkpoint (recompute_optimizer.py analogue).
        self.grad_transform = grad_transform
        self.strategy_state = strategy_state if strategy_state is not None \
            else {}
        # numeric-integrity sentry (observability.sentry.NumericSentry):
        # per-scope grad/param stats + the every-K fingerprint probe
        # compile INTO the one step program as scalar outputs; the
        # host-side monitor turns them into sentry.* gauges and
        # flight-recorder anomaly events. None = the program is
        # bit-identical to a sentry-less build (gate-down guard).
        self.sentry = sentry
        if sentry is not None:
            sentry.init_state(self.strategy_state)
        self.remat = remat
        self.remat_policy = remat_policy

        state = layer.state_dict()
        self._trainable_names = [k for k, t in state.items()
                                 if not t.stop_gradient]
        self._buffer_names = [k for k, t in state.items() if t.stop_gradient]
        self.params = {k: state[k]._data for k in self._trainable_names}
        self.buffers = {k: state[k]._data for k in self._buffer_names}
        # name -> live Tensor, so every step can re-point the Layer's
        # tensors at the freshly-returned arrays (zero-copy pointer
        # swap). Without this, the donated step deletes the arrays the
        # Layer still references and any later eager use of the model
        # (predict after training — ordinary dygraph flow) dies with
        # "Array has been deleted".
        self._state_tensors = dict(state)
        # abstract (meta-init) layer: params are ShapeDtypeStructs — the
        # step can only be AOT-lowered (aot_lower), never executed;
        # optimizer state stays abstract via eval_shape
        self._abstract = any(
            isinstance(v, jax.ShapeDtypeStruct)
            for v in self.params.values())
        if self._abstract:
            if amp_level == "O2":
                dt = jnp.dtype(amp_dtype)
                self.params = {
                    k: (jax.ShapeDtypeStruct(v.shape, dt)
                        if jnp.issubdtype(v.dtype, jnp.floating) else v)
                    for k, v in self.params.items()}
                if not optimizer._multi_precision:
                    optimizer._multi_precision = True
            self.opt_state = jax.eval_shape(optimizer.init_state_tree,
                                            self.params)
        elif amp_level == "O2":
            # pure-low-precision mode (reference amp O2 / pure_fp16):
            # params themselves are cast down; the optimizer keeps fp32
            # masters (multi_precision is mandatory for fp16 training)
            dt = jnp.dtype(amp_dtype)
            orig = dict(self.params)
            self.params = {
                k: v.astype(dt) if jnp.issubdtype(v.dtype, jnp.floating)
                else v
                for k, v in self.params.items()}
            if not optimizer._multi_precision:
                optimizer._multi_precision = True
            self.opt_state = optimizer.init_state_tree(self.params)
            # masters must come from the ORIGINAL fp32 values, not the
            # cast-down params (adam.py multi_precision keeps full
            # precision; round-tripping through fp16 would quantize
            # every weight at init)
            for k, st in self.opt_state.items():
                if isinstance(st, dict) and "master_weight" in st:
                    st["master_weight"] = orig[k].astype(jnp.float32)
        else:
            self.opt_state = optimizer.init_state_tree(self.params)
        if self._scaler_cfg is not None:
            cfg = self._scaler_cfg
            self.strategy_state.setdefault(
                "amp_scale", jnp.asarray(cfg["init_scale"], jnp.float32))
            self.strategy_state.setdefault("amp_good",
                                           jnp.asarray(0, jnp.int32))
            self.strategy_state.setdefault("amp_bad",
                                           jnp.asarray(0, jnp.int32))
            # cumulative skipped-step count, accumulated IN-GRAPH: the
            # always-available ground truth for loss-scale skips that
            # needs no host sync and rides every checkpoint
            self.strategy_state.setdefault("amp_skipped",
                                           jnp.asarray(0, jnp.int32))
        self._accum_grads = None
        self._accum_count = 0
        self._steps_done = 0
        self._donate = donate
        self._step_fn = None  # built lazily (data shardings need structure)
        self._grad_fn = None
        # one-train-executable guard, observed every step (always-on —
        # the counter bypasses the metrics gate)
        self.recompile_sentinel = RecompileSentinel("train")
        if self.mesh is not None and self.sharding_plan is not None \
                and not self._abstract:
            # place params/opt-state/buffers per the plan up front
            # (abstract states can't be device_put; aot_lower's
            # in_shardings carry the placement instead)
            plan = self.sharding_plan
            state = layer.state_dict()
            self.params = {
                k: plan.place(v, plan.param_spec(k, state.get(k)))
                for k, v in self.params.items()}
            # scalars (beta-pow accumulators) and buffers are placed
            # too, replicated as step_shardings declares them: left
            # uncommitted they come back from step 1 committed to the
            # mesh, a new jit signature — a retrace at step 2 and a
            # second cache entry
            rep = jax.sharding.PartitionSpec()
            self.opt_state = {
                k: {n: plan.place(v, plan.state_spec(k, state.get(k))
                                  if np.ndim(v) > 0 else rep)
                    for n, v in st.items()}
                for k, st in self.opt_state.items()}
            self.buffers = {k: plan.place(v, rep)
                            for k, v in self.buffers.items()}

    # -- pure step ----------------------------------------------------------
    def _forward_loss(self, params, buffers, key, inputs, labels):
        layer = self.layer
        state = layer.state_dict()
        saved = {k: t._data for k, t in state.items()}
        try:
            for k, a in params.items():
                state[k]._data = a
            for k, a in buffers.items():
                state[k]._data = a
            ctx = key_scope(key)
            from ..amp.auto_cast import auto_cast
            with no_grad(), ctx, self._mesh_scope():
                if self.amp_level:
                    with auto_cast(level=self.amp_level,
                                   dtype=self.amp_dtype):
                        out = layer(*_wrap_tree(inputs))
                        loss = self.loss_fn(out, *_wrap_tree(labels))
                else:
                    out = layer(*_wrap_tree(inputs))
                    loss = self.loss_fn(out, *_wrap_tree(labels))
            new_buffers = {k: state[k]._data for k in self._buffer_names}
            return (loss._data.astype(jnp.float32),
                    (new_buffers, _unwrap_tree(out)))
        finally:
            for k, a in saved.items():
                state[k]._data = a

    def _mesh_scope(self):
        """The forward traces under the step's mesh so kernels GSPMD
        cannot partition shard_map themselves; a no-op off-mesh."""
        if self.mesh is None or self.sharding_plan is None:
            return contextlib.nullcontext()
        from ..distributed.env import step_mesh
        return step_mesh(self.mesh, self.sharding_plan.data_axes)

    def _build(self, in_arrays, lbl_arrays):
        optimizer = self.optimizer
        accum = self.grad_accum_steps
        fwd_loss = self._forward_loss
        if self.remat:
            fwd_loss = jax.checkpoint(
                self._forward_loss, policy=self.remat_policy,
                static_argnums=())

        scaler_cfg = self._scaler_cfg

        def step(params, opt_state, buffers, strat, key, lr, inputs,
                 labels):
            scale = strat["amp_scale"] if scaler_cfg is not None else None

            def scaled_loss(p, b, k, i, l):
                loss, aux = fwd_loss(p, b, k, i, l)
                if scale is not None:
                    loss = loss * scale
                return loss, aux

            if accum > 1:
                # gradient merge (reference gradient_merge_optimizer.py):
                # split the batch into accum microbatches, scan, average
                def micro(idx):
                    sl = jax.tree_util.tree_map(
                        lambda a: _microslice(a, idx, accum), inputs)
                    ll = jax.tree_util.tree_map(
                        lambda a: _microslice(a, idx, accum), labels)
                    k = jax.random.fold_in(key, idx)
                    gf = jax.value_and_grad(
                        lambda p: scaled_loss(p, buffers, k, sl, ll),
                        has_aux=True)
                    return gf

                def body(carry, idx):
                    g_acc, l_acc = carry
                    (loss, (nb, _)), grads = micro(idx)(params)
                    g_acc = jax.tree_util.tree_map(
                        lambda a, b: a + b, g_acc, grads)
                    return (g_acc, l_acc + loss), nb
                zero_g = jax.tree_util.tree_map(jnp.zeros_like, params)
                (g_sum, l_sum), nbs = jax.lax.scan(
                    body, (zero_g, jnp.zeros((), jnp.float32)),
                    jnp.arange(accum))
                grads = jax.tree_util.tree_map(lambda a: a / accum, g_sum)
                loss = l_sum / accum
                new_buffers = jax.tree_util.tree_map(
                    lambda a: a[-1], nbs)
            else:
                grad_fn = jax.value_and_grad(
                    lambda p: scaled_loss(p, buffers, key, inputs,
                                          labels), has_aux=True)
                (loss, (new_buffers, _)), grads = grad_fn(params)
            found_inf = None
            if scale is not None:
                from ..amp.functional import (check_finite_and_unscale_tree,
                                              update_loss_scaling_state)
                with _scope("loss_scale"):
                    grads, found_inf = check_finite_and_unscale_tree(
                        grads, scale)
                    loss = loss / scale
            # PRE-SYNC grads: the sentry's per-rank tell — after the
            # grad_transform's collective every replica holds the same
            # (possibly already-poisoned) values and nothing can name
            # the chip that produced the corruption
            pre_sync_grads = grads
            if self.grad_transform is not None:
                grads, strat = self.grad_transform(grads, strat, params)
            with _scope("optimizer"):
                new_params, new_opt = optimizer.apply_gradients_tree(
                    params, grads, opt_state, lr=lr)
            if found_inf is not None:
                # skipped-step semantics: on overflow keep params and
                # optimizer state exactly as they were
                with _scope("loss_scale"):
                    keep = lambda new, old: jax.tree_util.tree_map(
                        lambda n, o: jnp.where(found_inf, o, n), new, old)
                    new_params = keep(new_params, params)
                    new_opt = keep(new_opt, opt_state)
                    strat = dict(strat)
                    if scaler_cfg["dynamic"]:
                        ns, ng, nb = update_loss_scaling_state(
                            scale, strat["amp_good"], strat["amp_bad"],
                            found_inf,
                            incr_ratio=scaler_cfg["incr_ratio"],
                            decr_ratio=scaler_cfg["decr_ratio"],
                            incr_every_n=scaler_cfg["incr_every_n"],
                            decr_every_n=scaler_cfg["decr_every_n"])
                        strat.update(amp_scale=ns, amp_good=ng,
                                     amp_bad=nb)
            # tiny scalar extras riding the step's existing results
            # (zero additional dispatches, still ONE executable):
            # amp skip visibility + the numeric sentry's stat streams
            extras: Dict[str, Any] = {}
            if found_inf is not None:
                with _scope("loss_scale"):
                    strat = dict(strat)
                    strat["amp_skipped"] = (
                        strat["amp_skipped"]
                        + found_inf.astype(jnp.int32))
                    extras["amp"] = {"found_inf": found_inf,
                                     "scale": strat["amp_scale"]}
            if self.sentry is not None:
                s_out, strat = self.sentry.instrument(
                    pre_sync_grads, new_params, loss, strat)
                extras["sentry"] = s_out
            return new_params, new_opt, new_buffers, strat, loss, extras

        jit_kwargs = {}
        if self._donate:
            jit_kwargs["donate_argnums"] = (0, 1, 2, 3)
        if self.mesh is not None and self.sharding_plan is not None:
            plan = self.sharding_plan
            in_sh, out_sh = plan.step_shardings(self)
            data_in = jax.tree_util.tree_map(
                lambda a: plan.named(plan.data_spec(a)), in_arrays)
            lbl_in = jax.tree_util.tree_map(
                lambda a: plan.named(plan.data_spec(a)), lbl_arrays)
            jit_kwargs["in_shardings"] = in_sh + (data_in, lbl_in)
            jit_kwargs["out_shardings"] = out_sh
        return jax.jit(step, **jit_kwargs)

    # -- AOT lowering (memory receipts) -------------------------------------
    def aot_lower(self, inputs, labels=(), lowering_platforms=None):
        """Lower (and let the caller .compile()) the full training step
        from avals alone — no parameter, optimizer-state, or activation
        bytes are ever allocated. Pairs with
        utils.abstract_init.abstract_parameters() for models too big to
        materialize; `compiled.memory_analysis()` then yields the
        per-device peak the step would need — the hardware-independent
        fits-in-HBM receipt (tests/test_memory_receipts.py).
        `lowering_platforms=("tpu",)` over a mesh of compile-only
        topology devices lowers for the chip from a CPU host
        (tests/test_pallas_mosaic_compile.py)."""
        def aval(x):
            if isinstance(x, jax.ShapeDtypeStruct):
                return x
            return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype)
        inputs = inputs if isinstance(inputs, (list, tuple)) else (inputs,)
        labels = labels if isinstance(labels, (list, tuple)) else (labels,)
        in_avals = jax.tree_util.tree_map(aval, tuple(inputs))
        lbl_avals = jax.tree_util.tree_map(aval, tuple(labels))
        step = self._build(in_avals, lbl_avals)
        key_aval = jax.eval_shape(lambda: jax.random.key(0))
        lr_aval = jax.ShapeDtypeStruct((), jnp.float32)
        strat_avals = jax.tree_util.tree_map(aval, self.strategy_state)
        buf_avals = jax.tree_util.tree_map(aval, self.buffers)
        opt_avals = jax.tree_util.tree_map(aval, self.opt_state)
        param_avals = jax.tree_util.tree_map(aval, self.params)
        return step.trace(
            param_avals, opt_avals, buf_avals, strat_avals, key_aval,
            lr_aval, in_avals, lbl_avals).lower(
                lowering_platforms=lowering_platforms)

    # -- eval / predict -----------------------------------------------------
    def build_eval_fn(self):
        def ev(params, buffers, key, inputs):
            layer = self.layer
            state = layer.state_dict()
            saved = {k: t._data for k, t in state.items()}
            mode = layer.training
            try:
                layer.eval()
                for k, a in {**params, **buffers}.items():
                    state[k]._data = a
                with no_grad(), key_scope(key):
                    out = layer(*_wrap_tree(inputs))
                return _unwrap_tree(out)
            finally:
                layer.training = mode
                for lyr in layer.sublayers(include_self=True):
                    lyr.training = mode
                for k, a in saved.items():
                    state[k]._data = a
        return jax.jit(ev)

    # -- the step call ------------------------------------------------------
    def __call__(self, inputs, labels=()):
        inputs = inputs if isinstance(inputs, (list, tuple)) else (inputs,)
        labels = labels if isinstance(labels, (list, tuple)) else (labels,)
        in_arrays = _unwrap_tree(tuple(inputs))
        lbl_arrays = _unwrap_tree(tuple(labels))
        if self._step_fn is None:
            self._step_fn = self._build(in_arrays, lbl_arrays)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        key = next_key()
        # black-box step bracket (one bool read when disabled): the
        # flight recorder's step events drive the hang watchdog's
        # progress clock and the goodput "train" bucket
        _tok = _fr.step_begin("train_step", self._steps_done)
        try:
            (self.params, self.opt_state, self.buffers,
             self.strategy_state, loss, extras) = self._step_fn(
                self.params, self.opt_state, self.buffers,
                self.strategy_state, key, lr, in_arrays, lbl_arrays)
        except Exception as e:
            # OOM sentry (memory plane): zero cost unless the dispatch
            # actually dies — a RESOURCE_EXHAUSTED leaves the always-on
            # counter, the flight-recorder `oom` breadcrumb and a
            # post-mortem receipt (top scopes + remediation hint)
            # before the fault propagates
            _mem.handle_dispatch_oom("train_step", e,
                                     step=self._steps_done)
            raise
        if _tok is not None and _fr.sync_steps():
            # device-complete before the bracket closes, so step.end
            # durations measure real work, not async dispatch latency
            jax.block_until_ready(loss)
        _fr.step_end("train_step", self._steps_done, _tok)
        if "amp" in extras and (_obs._enabled or _fr._enabled):
            # loss-scale skip visibility: the found_inf branch keeps
            # params/opt-state untouched — a silent no-op step unless
            # someone says so. The host read is GATED on an armed
            # observability plane: a per-step device sync would break
            # the no-host-sync contract of the in-graph scaler on the
            # hottest path. The ungated ground truth is the in-graph
            # cumulative strategy_state["amp_skipped"] (checkpointed,
            # readable at any sync point with zero per-step cost).
            skipped = bool(np.asarray(extras["amp"]["found_inf"]))
            scale_v = float(np.asarray(extras["amp"]["scale"]))
            if skipped:
                _obs.counter("amp.loss_scale.skipped_total",
                             _always=True).add(1)
                _fr.record("loss_scale.skip", step=self._steps_done,
                           scale=scale_v)
            if _obs._enabled:
                _obs.gauge("amp.loss_scale.scale").set(scale_v)
        if self.sentry is not None:
            self.sentry.consume(self._steps_done, extras["sentry"])
        self._steps_done += 1
        if isinstance(self.optimizer._lr, LRScheduler):
            pass  # caller steps the scheduler per its own schedule
        if _obs._enabled:
            _obs.counter("train.steps_total").add(1)
        # sentinel is ALWAYS on (counter bypasses the metrics gate): a
        # silent retrace is a contract violation whether or not anyone
        # is scraping; cost is one cache-size read + input-shapes walk
        self.recompile_sentinel.observe(
            int(self._step_fn._cache_size()), expected=1,
            signature=signature_of((in_arrays, lbl_arrays)))
        # keep the Layer's tensors pointing at live (undonated) arrays —
        # dygraph semantics: the model is usable eagerly at any time
        self.sync_to_layer()
        return Tensor(loss)

    def sync_to_layer(self):
        """Re-point the Layer's Tensors at the step's live arrays
        (zero-copy). Called after every step — the donated executable
        deletes the arrays the Layer previously referenced — and kept
        public for checkpoint/restore flows."""
        st = self._state_tensors
        for k, a in self.params.items():
            st[k]._data = a
        for k, a in self.buffers.items():
            st[k]._data = a

    def rebind_layer(self):
        """Re-resolve the Tensor cache against the LIVE layer. The
        per-step sync_to_layer uses a construction-time name->Tensor
        cache (an O(tensors) state_dict() walk per step would be
        hot-loop drag); if the layer's tensors are REPLACED after
        construction (re-init, sublayer swap, quant convert()), that
        cache feeds orphaned Tensor objects while the live layer keeps
        donated/deleted arrays. Checkpoint flows call this; call it
        yourself after any in-place layer surgery while a TrainStep is
        bound."""
        live = self.layer.state_dict()
        for k, t in live.items():
            if k in self._state_tensors:
                self._state_tensors[k] = t

    def state_dict(self):
        self.rebind_layer()
        self.sync_to_layer()
        return {"model": self.layer.state_dict(),
                "opt_state": self.opt_state,
                "opt": self.optimizer.state_dict(),
                "strategy_state": self.strategy_state}

    def set_state_dict(self, state):
        """Restore a state_dict() checkpoint (params/buffers into the
        layer, optimizer + strategy state — DGC error-feedback buffers,
        rampup counters — into the step). Arrays are COPIED: the compiled
        step donates its state buffers each call, so sharing them with the
        checkpoint source would invalidate the source's state."""
        self.rebind_layer()
        def copy_arr(v):
            a = v._data if isinstance(v, Tensor) else v
            return jnp.array(np.asarray(a))
        model = state.get("model") or {}
        own = self.layer.state_dict()
        for k, v in model.items():
            arr = copy_arr(v)
            if k in own:
                own[k]._data = arr
            if k in self.params:
                self.params[k] = arr
            if k in self.buffers:
                self.buffers[k] = arr
        if state.get("opt_state") is not None:
            self.opt_state = jax.tree_util.tree_map(copy_arr,
                                                    state["opt_state"])
        if state.get("opt") is not None:
            self.optimizer.set_state_dict(state["opt"])
        if state.get("strategy_state") is not None:
            self.strategy_state = jax.tree_util.tree_map(
                copy_arr, state["strategy_state"])
            # re-seed the keys THIS build requires that the restored
            # candidate may predate (a pre-sentry checkpoint, an
            # amp run older than the in-graph skip counter): the
            # wholesale replace must never hand the compiled step a
            # strategy pytree missing the keys it was traced with —
            # that KeyErrors inside the very rollback the numeric
            # remediation performs
            if self._scaler_cfg is not None:
                cfg = self._scaler_cfg
                self.strategy_state.setdefault(
                    "amp_scale",
                    jnp.asarray(cfg["init_scale"], jnp.float32))
                self.strategy_state.setdefault(
                    "amp_good", jnp.asarray(0, jnp.int32))
                self.strategy_state.setdefault(
                    "amp_bad", jnp.asarray(0, jnp.int32))
                self.strategy_state.setdefault(
                    "amp_skipped", jnp.asarray(0, jnp.int32))
            if self.sentry is not None:
                self.sentry.init_state(self.strategy_state)
        else:
            # rollback consistency: int8-EF residuals are time-coupled
            # to the params they quantized — restoring params WITHOUT
            # the matching strategy state must purge live residuals
            # (reset is unbiased; a residual from the rolled-back
            # future is not)
            from ..distributed.comm import purge_residual_state
            purge_residual_state(self.strategy_state)
