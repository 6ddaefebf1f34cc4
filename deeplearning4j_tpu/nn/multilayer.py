"""MultiLayerNetwork: sequential network runtime.

Reference: ``nn/multilayer/MultiLayerNetwork.java`` (3,545 LoC) — init with
flattened params (``:584-718``), training loop (``fit(DataSetIterator)
:1268``), backprop (``:1363``), ``computeGradientAndScore():2360``,
inference (``output:2031``), rnn stepping, tBPTT (``:1315-1317``).

TPU-native design: the entire step — forward, backward, gradient
normalization, regularization, updater math, parameter update, constraints
— is ONE jit-compiled XLA program with donated buffers (the functional
equivalent of the reference's in-place flattened-view update,
``StochasticGradientDescent.java:78``). This removes the per-op JNI
dispatch that defines the reference's hot loop (SURVEY.md §3.1) and lets
XLA fuse elementwise work into the MXU matmuls.

State layout:
- ``self.params_``: list (per layer) of dicts name→array
- ``self.state_``:  list of dicts (BN running stats, center-loss centers)
- ``self.opt_state_``: list of dicts name→updater-state-dict
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import (
    AsyncDataSetIterator,
    DataSetIterator,
    ListDataSetIterator,
)
from deeplearning4j_tpu.nn.conf.builders import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf.layers.base import (
    Layer,
    apply_input_dropout,
    apply_weight_noise,
)
from deeplearning4j_tpu.nn.conf.layers.recurrent import BaseRecurrentLayer
from deeplearning4j_tpu.nn.conf.layers.special import CenterLossOutputLayer, FrozenLayer
from deeplearning4j_tpu.regularization import normalize_layer_gradients
from deeplearning4j_tpu.obs import trace as _trace
from deeplearning4j_tpu.updaters import NoOp

Array = jax.Array

# host phases of one training step (obs/trace.py): what the input pipeline
# costs the loop, the host arrays going to the device, everything that
# launches device work, and what the program itself reads back
_ITERATE = _trace.phase("train.iterate")
_PUT_BATCH = _trace.phase("train.put_batch")
_DISPATCH = _trace.phase("train.dispatch")
_FETCH_LOSS = _trace.phase("train.fetch_loss")


def _dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16,
            "float64": jnp.float64}[name]


def _cast_layer_params_for_compute(layer, p, cd, *, is_output: bool):
    """Mixed-precision compute cast for one layer's param dict: float params
    → ``cd``, except normalization layers (stats/scale stay fp32 for
    stability) and output layers (loss/softmax in fp32). The cast happens
    inside the differentiated function, so its transpose casts gradients
    back to fp32 — master weights and updater math stay full precision.
    Shared by MultiLayerNetwork and ComputationGraph."""
    from deeplearning4j_tpu.nn.conf.layers.norm import (
        BatchNormalization,
        LocalResponseNormalization,
    )

    if isinstance(layer, (BatchNormalization, LocalResponseNormalization)) or is_output:
        return p
    keep = getattr(layer, "keep_fp32_params", ())
    return {
        k: v.astype(cd)
        if jnp.issubdtype(v.dtype, jnp.floating) and k not in keep else v
        for k, v in p.items()
    }


def _resolve_remat_policy(name):
    """GlobalConf.remat_policy (or DL4J_TPU_REMAT env override) → a
    jax.checkpoint policy, or None for no rematerialization."""
    import os

    name = os.environ.get("DL4J_TPU_REMAT") or name
    if not name or name == "none":
        return None
    from jax import checkpoint_policies as cp

    if name == "save_conv_outputs":
        return cp.save_only_these_names("conv_out")
    if name == "dots":
        return cp.dots_saveable
    if name == "nothing":
        return cp.nothing_saveable
    raise ValueError(f"unknown remat_policy: {name!r}")


def _apply_layer_updates(layers, params, grads, opt_state, t, iteration, epoch):
    """Shared per-layer update pipeline (both train steps): gradient
    normalization → l1/l2/weight-decay → updater → constraints.

    Order matches the reference (``BaseMultiLayerUpdater.update``: preApply
    normalization, then UpdaterBlock regularization + updater math, then
    ``BaseOptimizer.applyConstraints``)."""
    new_params, new_opt = [], []
    for i, layer in enumerate(layers):
        p_i, g_i, o_i = params[i], grads[i], opt_state[i]
        if isinstance(layer, FrozenLayer) or not p_i:
            new_params.append(p_i)
            new_opt.append(o_i)
            continue
        g_i = normalize_layer_gradients(
            g_i, layer.gradient_normalization, layer.gradient_normalization_threshold
        )
        reg = layer.regularization
        if reg is not None:
            out = {}
            for k, g in g_i.items():
                term = reg.grad_term(k, p_i[k])
                out[k] = g if term is None else g + term
            g_i = out
        upd = layer.updater if layer.updater is not None else NoOp()
        np_i, no_i = {}, {}
        for name, g in g_i.items():
            delta, new_slot = upd.apply(g, o_i[name], t, iteration, epoch)
            np_i[name] = p_i[name] - delta
            no_i[name] = new_slot
        for c in layer.constraints:
            for name in np_i:
                if name in c.applies_to:
                    np_i[name] = c.apply(np_i[name])
        new_params.append(np_i)
        new_opt.append(no_i)
    return new_params, new_opt


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration, *,
                 copy_conf: bool = True):
        import copy

        # Own a private copy of the configuration: layers (and their
        # updaters/schedules) are mutable, and e.g. set_learning_rate
        # must not silently retune a sibling network built from the same
        # conf object. Within THIS network, self.layers and
        # self.conf.layers stay the same objects so to_json() always
        # serializes the live hyperparameters. copy_conf=False is for
        # callers that just built a conf nothing else holds (clone()'s
        # JSON round-trip) — skips the redundant deepcopy.
        if copy_conf:
            conf = copy.deepcopy(conf)
        self.conf = conf
        self.layers: List[Layer] = conf.layers
        self.params_: Optional[List[Dict[str, Array]]] = None
        self.state_: Optional[List[Dict[str, Array]]] = None
        self.opt_state_: Optional[List[Dict[str, Any]]] = None
        self.iteration = 0
        self.epoch = 0
        self.score_: Optional[Array] = None  # device scalar; float(score()) syncs
        # fault-tolerance carry (train/faults.py): bad/consecutive/good
        # step counters + dynamic loss scale, all device scalars
        self.fault_state_: Optional[Dict[str, Array]] = None
        self.listeners: List[Any] = []
        self._rng = jax.random.PRNGKey(conf.global_conf.seed)
        self._rnn_carries: Optional[List[Any]] = None
        self._jit_cache: Dict[str, Any] = {}
        # input-pipeline provenance + device-side augmentation
        # (data/loader.py, data/augment.py): _data_state rides in
        # checkpoint meta.json next to the RNG chain
        self._data_state: Optional[Dict[str, Any]] = None
        self._augment = None
        cd = getattr(conf.global_conf, "compute_dtype", None)
        self._compute_dtype = None if cd is None else _dtype_of(cd)

    # ---------------------------------------------------------- fault policy
    def _active_fault_policy(self):
        """The FaultPolicy iff configured AND it has work to do for this
        model (see train/faults.active_policy)."""
        from deeplearning4j_tpu.train import faults

        return faults.active_policy(
            getattr(self.conf.global_conf, "fault_policy", None),
            self._compute_dtype,
        )

    def _ensure_fault_state(self, policy):
        from deeplearning4j_tpu.train import faults

        scaling = policy.scaling_active(self._compute_dtype)
        if (self.fault_state_ is None
                or ("loss_scale" in self.fault_state_) != scaling):
            self.fault_state_ = faults.init_fault_state(
                policy, scaling, start_step=self.iteration)
        return self.fault_state_

    def set_fault_policy(self, policy) -> None:
        """Install (or clear, with None) the training fault policy; takes
        effect on the next step — compiled steps closed over the old
        policy are invalidated."""
        self.conf.global_conf.fault_policy = policy
        self.fault_state_ = None
        self._jit_cache.clear()

    @property
    def bad_step_count(self) -> int:
        """Lifetime count of skipped (non-finite gradient) steps."""
        return 0 if self.fault_state_ is None else int(
            self.fault_state_["bad_count"])

    @property
    def loss_scale(self) -> Optional[float]:
        """Current dynamic loss scale, or None when scaling is off."""
        if self.fault_state_ is None or "loss_scale" not in self.fault_state_:
            return None
        return float(self.fault_state_["loss_scale"])

    def _cast_for_compute(self, params):
        cd = self._compute_dtype
        if cd is None:
            return params
        n = len(self.layers)
        return [
            _cast_layer_params_for_compute(
                layer, p, cd, is_output=(i == n - 1 and layer.is_output_layer)
            )
            for i, (layer, p) in enumerate(zip(self.layers, params))
        ]

    # ------------------------------------------------------------------ init
    def init(self, rng: Optional[Array] = None) -> "MultiLayerNetwork":
        """Allocate parameters (reference ``MultiLayerNetwork.init()``)."""
        if self.conf.input_type is None:
            raise ValueError("Configuration needs set_input_type(...) before init()")
        rng = rng if rng is not None else jax.random.PRNGKey(self.conf.global_conf.seed)
        dtype = _dtype_of(self.conf.global_conf.dtype)
        types = self.conf.layer_types()
        params, state, opt_state = [], [], []
        keys = jax.random.split(rng, len(self.layers))
        for i, layer in enumerate(self.layers):
            p = layer.init_params(keys[i], types[i], dtype)
            s = layer.init_layer_state(types[i], dtype)
            params.append(p)
            state.append(s)
            upd = layer.updater if layer.updater is not None else NoOp()
            opt_state.append({name: upd.init_state(arr) for name, arr in p.items()})
        self.params_ = params
        self.state_ = state
        self.opt_state_ = opt_state
        self.iteration = 0
        self.epoch = 0
        return self

    # ------------------------------------------------------------- forward fn
    def _forward(
        self,
        params,
        state,
        x,
        *,
        train: bool,
        rng: Optional[Array],
        fmask=None,
        stop_before: Optional[int] = None,
        carries: Optional[List[Any]] = None,
        collect: bool = False,
    ):
        """Pure forward pass.

        Returns (x, mask, new_states, new_carries, activations) where x is
        the activation *into* layer ``stop_before`` (after its preprocessor
        and input-dropout) or the final output if stop_before is None.
        """
        n = len(self.layers)
        stop = n if stop_before is None else stop_before
        if self._compute_dtype is not None:
            params = self._cast_for_compute(params)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
                x = jnp.asarray(x).astype(self._compute_dtype)
        rngs = (
            jax.random.split(rng, n) if rng is not None else [None] * n
        )
        mask = fmask
        new_states: List[Dict[str, Array]] = []
        new_carries: List[Any] = [None] * n
        acts = []
        for i in range(n):
            layer = self.layers[i]
            if i in self.conf.preprocessors:
                prep = self.conf.preprocessors[i]
                x = prep.pre_process(x, mask)
                mask = prep.feed_forward_mask(mask)
            x = apply_input_dropout(layer, x, train, rngs[i])
            if i >= stop:
                break
            p_i = apply_weight_noise(layer, params[i], train, rngs[i])
            if (
                carries is not None
                and isinstance(layer, BaseRecurrentLayer)
                and carries[i] is not None
            ):
                x, c = layer.apply_with_carry(
                    p_i, x, carries[i], mask=mask, train=train, rng=rngs[i]
                )
                new_carries[i] = c
                st = state[i]
            else:
                x, st = layer.apply(
                    p_i, x, state=state[i], train=train, rng=rngs[i], mask=mask
                )
            new_states.append(st if st is not None else {})
            if collect:
                acts.append(x)
            if layer.is_recurrent and mask is not None:
                pass  # recurrent layers preserve (b, T) masks
            elif x.ndim == 2 and mask is not None and mask.ndim > 1:
                mask = None  # mask consumed by pooling/last-step layers
        return x, mask, new_states, new_carries, acts

    def _output_layer(self):
        last = self.layers[-1]
        if not last.is_output_layer:
            raise ValueError(f"Last layer {last} is not an output layer")
        return last

    # ---------------------------------------------------------------- scoring
    def _loss_and_new_state(self, params, state, features, labels, fmask, lmask, rng, train=True):
        n = len(self.layers)
        x, mask, new_states, _, _ = self._forward(
            params, state, features, train=train, rng=rng, fmask=fmask, stop_before=n - 1
        )
        if self._compute_dtype is not None:
            x = x.astype(jnp.float32)  # loss/softmax in full precision
        out_layer = self._output_layer()
        label_mask = lmask if lmask is not None else mask
        # weight noise on the output layer: the forward stops before it,
        # so noise the params here (reference applies getParamsWithNoise
        # to output layers too)
        p_out = apply_weight_noise(out_layer, params[-1],
                                   train and rng is not None, rng)
        if isinstance(out_layer, CenterLossOutputLayer):
            per_ex = out_layer.compute_score(p_out, x, labels, label_mask, state=state[-1])
            new_last_state = out_layer.update_centers(state[-1], x, labels) if train else state[-1]
        else:
            per_ex = out_layer.compute_score(p_out, x, labels, label_mask)
            new_last_state = state[-1]
        new_states.append(new_last_state)
        loss = jnp.mean(per_ex)
        # auxiliary layer losses (MoE load-balancing) ride the state pytree
        for st in new_states:
            if isinstance(st, dict) and "aux_loss" in st:
                loss = loss + st["aux_loss"]
        return loss, new_states

    def _reg_score(self, params):
        s = jnp.asarray(0.0, jnp.float32)
        for i, layer in enumerate(self.layers):
            reg = layer.regularization
            if reg is None:
                continue
            for name, arr in params[i].items():
                s = s + reg.score_term(name, arr)
        return s

    # ------------------------------------------------------------- train step
    def train_step_fn(self, telemetry=None):
        """The raw (unjitted) pure train step — reused by the data-parallel
        wrapper which jits it with mesh shardings (parallel/wrapper.py).
        ``telemetry`` (obs/telemetry.TelemetryConf) appends a per-step
        in-graph telemetry dict to the outputs."""
        return self._make_train_step(jit=False, telemetry=telemetry)

    def _make_train_step(self, jit: bool = True, telemetry=None):
        layers = self.layers

        remat_policy = _resolve_remat_policy(
            getattr(self.conf.global_conf, "remat_policy", None)
        )
        policy = self._active_fault_policy()
        if telemetry is not None:
            from deeplearning4j_tpu.obs import telemetry as _obs_telemetry

        def _jit(fn):
            from deeplearning4j_tpu.train import faults as _faults

            # telemetry's extra reads (update norm = new - old) are plain
            # dataflow XLA sequences before reusing donated buffers; the
            # guard_donation CPU gate stays scoped to the guarded steps'
            # where-select aliasing pattern (the observed miscompile)
            donate = (_faults.guard_donation(0, 1, 2)
                      if policy is not None else (0, 1, 2))
            return jax.jit(
                _trace.count_retraces(f"{type(self).__name__}.train_step",
                                      fn),
                donate_argnums=donate)

        if policy is None:
            def step(params, opt_state, state, features, labels, fmask, lmask, rng, iteration, epoch):
                def loss_fn(p):
                    loss, new_states = self._loss_and_new_state(
                        p, state, features, labels, fmask, lmask, rng, train=True
                    )
                    return loss, new_states

                if remat_policy is not None:
                    loss_fn = jax.checkpoint(loss_fn, policy=remat_policy)
                (loss, new_states), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
                t = iteration + 1  # 1-based updater step for bias correction
                new_params, new_opt = _apply_layer_updates(
                    layers, params, grads, opt_state, t, iteration, epoch
                )
                score = loss + self._reg_score(params)
                if telemetry is not None:
                    telem = _obs_telemetry.step_telemetry(
                        telemetry, grads, params, new_params)
                    return new_params, new_opt, new_states, score, telem
                return new_params, new_opt, new_states, score

            return _jit(step) if jit else step

        # Guarded step (train/faults.py): loss scaling + global all-finite
        # verdict + jnp.where skip, bad/good counters carried in fstate.
        # The updater clock runs on the in-graph good_count so a skipped
        # batch leaves the trajectory exactly as if it had been removed.
        from deeplearning4j_tpu.train import faults as _faults

        scaling = policy.scaling_active(self._compute_dtype)
        do_skip = policy.skip_nonfinite or scaling

        def gstep(params, opt_state, state, fstate, features, labels, fmask,
                  lmask, rng, iteration, epoch):
            scale = fstate["loss_scale"] if scaling else None

            def loss_fn(p):
                loss, new_states = self._loss_and_new_state(
                    p, state, features, labels, fmask, lmask, rng, train=True
                )
                if scaling:
                    loss = loss * scale
                return loss, new_states

            if remat_policy is not None:
                loss_fn = jax.checkpoint(loss_fn, policy=remat_policy)
            (loss, new_states), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if scaling:
                inv = 1.0 / scale
                grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
                loss = loss * inv
            grads = _faults.inject_gradient_faults(grads, iteration)
            finite = _faults.all_finite(grads)
            t_good = fstate["good_count"]
            new_params, new_opt = _apply_layer_updates(
                layers, params, grads, opt_state, t_good + 1, t_good, epoch
            )
            if do_skip:
                new_params = _faults.where_tree(finite, new_params, params)
                new_opt = _faults.where_tree(finite, new_opt, opt_state)
                new_states = _faults.where_tree(finite, new_states, state)
            new_fstate = _faults.advance_fault_state(policy, fstate, finite)
            score = loss + self._reg_score(params)
            if telemetry is not None:
                telem = _obs_telemetry.step_telemetry(
                    telemetry, grads, params, new_params, fstate=new_fstate,
                    scale=scale)
                return (new_params, new_opt, new_states, new_fstate, score,
                        telem)
            return new_params, new_opt, new_states, new_fstate, score

        return _jit(gstep) if jit else gstep

    def _get_jit(self, key, maker):
        if key not in self._jit_cache:
            self._jit_cache[key] = maker()
        return self._jit_cache[key]

    # ------------------------------------------------------------------- fit
    def set_augmentation(self, stage) -> "MultiLayerNetwork":
        """Attach an :class:`~deeplearning4j_tpu.data.augment.AugmentStage`
        (or None to clear): a jitted device-side transform applied to
        every batch's features ahead of the train step. Keyed by
        iteration, so resumed fits replay the exact augmented stream."""
        self._augment = stage
        return self

    def fit(
        self,
        data: Union[DataSet, DataSetIterator, np.ndarray],
        labels: Optional[np.ndarray] = None,
        epochs: int = 1,
        batch_size: int = 32,
    ) -> "MultiLayerNetwork":
        """Train (reference ``fit(DataSetIterator):1268`` semantics incl.
        async prefetch and the tBPTT branch)."""
        if isinstance(data, np.ndarray) or isinstance(data, jnp.ndarray):
            data = DataSet(np.asarray(data), None if labels is None else np.asarray(labels))
        if isinstance(data, DataSet):
            it: DataSetIterator = ListDataSetIterator(data, batch_size)
        else:
            it = data
        from deeplearning4j_tpu.train.listeners import dispatch_fit_end
        try:
            for _ in range(epochs):
                self._fit_one_epoch(it)
        finally:
            # listeners holding open resources (an active ProfilerListener
            # trace window spanning the final partial epoch) close here —
            # including when an epoch raised
            dispatch_fit_end(self.listeners, self)
        return self

    def _fit_one_epoch(self, it: DataSetIterator):
        from deeplearning4j_tpu.data.iterators import BatchBundle, iter_bundled
        from deeplearning4j_tpu.train import pipeline as _pipeline

        for lst in self.listeners:
            if hasattr(lst, "on_epoch_start"):
                lst.on_epoch_start(self)
        k = _pipeline.resolve_steps_per_call(self)
        qsize = int(getattr(self.conf.global_conf, "async_queue_size", 4)
                    or 4)
        if k > 1:
            # queue depth counts SLOTS and each slot now stages K
            # device-resident batches — keep the prefetched-batch budget
            # (and the device memory it pins) at the k=1 level
            qsize = max(1, qsize // k)
        if it.async_supported():
            # bundling + H2D both move to the producer thread: batches are
            # stacked into K-step bundles and device_put there, so the
            # main thread only dispatches
            wrapped = AsyncDataSetIterator(it, queue_size=qsize,
                                           device_put=k > 1, bundle_size=k)
            stream = wrapped
        else:
            wrapped = it
            stream = iter_bundled(it, k) if k > 1 else it
        from deeplearning4j_tpu.obs import telemetry as _telemetry

        tconf = _telemetry.resolve(self)
        # cache key carries the conf CONTENTS: swapping TelemetryConf
        # fields between fits must rebuild, not reuse the old signals
        tkey = None if tconf is None else str(sorted(tconf.to_dict().items()))
        step = self._get_jit(
            ("train_telem", tkey) if tconf else "train",
            lambda: self._make_train_step(telemetry=tconf))
        bstep = (self._get_jit(
            ("train_bundle_telem", tkey) if tconf else "train_bundle",
            lambda: _pipeline.make_bundled_step(self, telemetry=tconf))
            if k > 1 else None)
        use_tbptt = self.conf.backprop_type == "tbptt"
        try:
            for ds in _trace.each_next(_ITERATE, stream):
                if isinstance(ds, BatchBundle):
                    self._fit_bundle(bstep, ds, tconf)
                elif use_tbptt and ds.features.ndim == 3:
                    self._fit_tbptt_batch(ds)
                else:
                    self._fit_batch(step, ds, tconf)
                # data-position provenance: the iterator's NEXT position
                # lands on the model AFTER the step that consumed the
                # pulled batches, so a checkpoint written between steps
                # resumes the stream exactly where this step left it
                _pipeline.capture_data_state(self, it)
        finally:
            if wrapped is not it:
                wrapped.shutdown()  # join prefetch thread; caller resets inner
        it.reset()
        _pipeline.capture_data_state(self, it)  # epoch-boundary position
        self.epoch += 1
        for lst in self.listeners:
            if hasattr(lst, "on_epoch_end"):
                lst.on_epoch_end(self)

    def _next_rng(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    def _make_introspect_fn(self):
        """(activations list, gradients) for one batch — the listener
        introspection pass (SURVEY §7 hard-part 1). Runs with the same
        rng the train step will consume, so reported values match the
        step bit-for-bit and attaching a listener never changes the
        training trajectory. The body mirrors the loss path exactly —
        including the output layer's score-path weight noise (unsplit
        rng, not the per-layer key a full forward would use)."""

        def run(params, state, f, l, fm, lm, rng):
            n = len(self.layers)
            x, mask, _, _, acts = self._forward(
                params, state, f, train=True, rng=rng, fmask=fm,
                stop_before=n - 1, collect=True)
            if self._compute_dtype is not None:
                x = x.astype(jnp.float32)
            out_layer = self._output_layer()
            p_out = apply_weight_noise(out_layer, params[-1], True, rng)
            y_out, _ = out_layer.apply(p_out, x, state=state[-1], train=True,
                                       rng=rng, mask=mask)
            acts = list(acts) + [y_out]

            def loss_fn(p):
                loss, _ = self._loss_and_new_state(
                    p, state, f, l, fm, lm, rng, train=True)
                return loss

            grads = jax.grad(loss_fn)(params)
            return acts, grads

        return jax.jit(run)

    def _run_introspection(self, features, labels, fmask, lmask, rng):
        from deeplearning4j_tpu.train.listeners import _hook_recipients

        it_next = self.iteration + 1
        fwd_to = _hook_recipients(self.listeners, "on_forward_pass", it_next)
        grad_to = _hook_recipients(self.listeners, "on_gradient_calculation",
                                   it_next)
        if not (fwd_to or grad_to):
            return
        fn = self._get_jit("introspect", self._make_introspect_fn)
        acts, grads = fn(self.params_, self.state_, features, labels,
                         fmask, lmask, rng)
        if fwd_to:
            acts_np = [np.asarray(a) for a in acts]
            for lst in fwd_to:
                lst.on_forward_pass(self, acts_np)
        if grad_to:
            grads_np = jax.tree_util.tree_map(np.asarray, grads)
            for lst in grad_to:
                lst.on_gradient_calculation(self, grads_np)

    def _fit_batch(self, step, ds: DataSet, tconf=None):
        from deeplearning4j_tpu.train.listeners import _hook_recipients

        _trace.set_cause(self.iteration)
        with _PUT_BATCH:
            features = jnp.asarray(ds.features)
            labels = None if ds.labels is None else jnp.asarray(ds.labels)
            fmask = (None if ds.features_mask is None
                     else jnp.asarray(ds.features_mask))
            lmask = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        with _DISPATCH:
            if self._augment is not None:
                # jitted device stage fused ahead of the train step —
                # iteration passed as a dynamic scalar (no retrace per step).
                # Batch-crossing stages (mixup) mix labels with the same
                # lam/permutation, so they take the pair path.
                if labels is not None and getattr(self._augment,
                                                  "mixes_labels", False):
                    features, labels = self._augment.apply_pair(
                        features, labels, self.iteration)
                else:
                    features = self._augment.apply(features, self.iteration)
            rng = self._next_rng()
            self._run_introspection(features, labels, fmask, lmask, rng)
            policy = self._active_fault_policy()
            telem = None
            with _trace.step_span("train", self.iteration):
                if policy is not None:
                    fstate = self._ensure_fault_state(policy)
                    out = step(
                        self.params_, self.opt_state_, self.state_, fstate,
                        features, labels, fmask, lmask, rng,
                        jnp.asarray(self.iteration, jnp.int32),
                        jnp.asarray(self.epoch, jnp.int32),
                    )
                    if tconf is not None:
                        *out, telem = out
                    (self.params_, self.opt_state_, self.state_,
                     self.fault_state_, self.score_) = out
                else:
                    out = step(
                        self.params_, self.opt_state_, self.state_,
                        features, labels, fmask, lmask, rng,
                        jnp.asarray(self.iteration, jnp.int32),
                        jnp.asarray(self.epoch, jnp.int32),
                    )
                    if tconf is not None:
                        *out, telem = out
                    (self.params_, self.opt_state_, self.state_,
                     self.score_) = out
        it0 = self.iteration
        self.iteration += 1
        self.last_batch_size = int(features.shape[0])
        if policy is not None or telem is not None or self.listeners:
            with _FETCH_LOSS:
                if policy is not None:
                    from deeplearning4j_tpu.train import faults as _faults

                    _faults.check_fault_state(policy, self.fault_state_, owner=self)
                if telem is not None:
                    from deeplearning4j_tpu.obs import telemetry as _telemetry

                    _telemetry.dispatch_telemetry(
                        self.listeners, self, it0, self.epoch,
                        _telemetry.BundleTelemetry(telem, 1))
                for lst in _hook_recipients(self.listeners, "on_backward_pass"):
                    lst.on_backward_pass(self)
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration, self.epoch)

    def _fit_bundle(self, bstep, bundle, tconf=None):
        """K optimizer steps in ONE dispatch (train/pipeline.py): the
        bundled lax.scan step consumes the stacked batches, advancing
        iteration and the fault-state carry in-graph; the divergence
        tripwire is checked once per bundle on the final ``consec``.
        With telemetry the stacked per-step signals ride the same
        dispatch and reach listeners through one deferred fetch."""
        from deeplearning4j_tpu.train import faults as _faults
        from deeplearning4j_tpu.train import pipeline as _pipeline

        k = bundle.k
        features = jnp.asarray(bundle.features)
        labels = None if bundle.labels is None else jnp.asarray(bundle.labels)
        if self._augment is not None:
            # per-inner-step keys fold it0+j, so bundled and unbundled
            # fits see identical per-iteration augmentation randomness
            if labels is not None and getattr(self._augment,
                                              "mixes_labels", False):
                features, labels = self._augment.apply_pair_bundle(
                    features, labels, self.iteration)
            else:
                features = self._augment.apply_bundle(features,
                                                      self.iteration)
        fmask = (None if bundle.features_mask is None
                 else jnp.asarray(bundle.features_mask))
        lmask = (None if bundle.labels_mask is None
                 else jnp.asarray(bundle.labels_mask))
        # same rng stream, same order as k single-step fits — bundled and
        # unbundled trajectories stay bit-identical
        rngs = jnp.stack([self._next_rng() for _ in range(k)])
        policy = self._active_fault_policy()
        it0 = self.iteration
        telem = None
        with _trace.step_span("train_bundle", it0):
            if policy is not None:
                fstate = self._ensure_fault_state(policy)
                out = bstep(
                    self.params_, self.opt_state_, self.state_, fstate,
                    features, labels, fmask, lmask, rngs,
                    jnp.asarray(it0, jnp.int32),
                    jnp.asarray(self.epoch, jnp.int32),
                )
                if tconf is not None:
                    *out, telem = out
                (self.params_, self.opt_state_, self.state_,
                 self.fault_state_, scores) = out
            else:
                out = bstep(
                    self.params_, self.opt_state_, self.state_,
                    features, labels, fmask, lmask, rngs,
                    jnp.asarray(it0, jnp.int32),
                    jnp.asarray(self.epoch, jnp.int32),
                )
                if tconf is not None:
                    *out, telem = out
                self.params_, self.opt_state_, self.state_, scores = out
        self.iteration += k
        self.score_ = scores[-1]
        self.last_batch_size = int(features.shape[1])
        if policy is not None:
            _faults.check_fault_state(policy, self.fault_state_, owner=self)
        _pipeline.dispatch_bundle_listeners(self, it0, self.epoch, scores,
                                            telem=telem)

    # ----------------------------------------------------------------- tBPTT
    def tbptt_step_fn(self):
        """Raw (unjitted) tBPTT chunk step — jitted with mesh shardings by
        the data-parallel wrapper."""
        return self._make_tbptt_step(jit=False)

    def _make_tbptt_step(self, jit: bool = True):
        layers = self.layers
        remat_policy = _resolve_remat_policy(
            getattr(self.conf.global_conf, "remat_policy", None)
        )
        policy = self._active_fault_policy()
        scaling = (policy is not None
                   and policy.scaling_active(self._compute_dtype))
        do_skip = policy is not None and (policy.skip_nonfinite or scaling)
        guarded = policy is not None

        def _body(params, opt_state, state, fstate, carries, features,
                  labels, fmask, lmask, rng, iteration, epoch):
            n = len(layers)
            scale = fstate["loss_scale"] if scaling else None

            def loss_fn(p):
                x, mask, new_states, new_carries, _ = self._forward(
                    p, state, features, train=True, rng=rng, fmask=fmask,
                    stop_before=n - 1, carries=carries,
                )
                if self._compute_dtype is not None:
                    x = x.astype(jnp.float32)
                out_layer = self._output_layer()
                label_mask = lmask if lmask is not None else mask
                p_out = apply_weight_noise(out_layer, p[-1], rng is not None, rng)
                per_ex = out_layer.compute_score(p_out, x, labels, label_mask)
                new_states.append(state[-1])
                loss = jnp.mean(per_ex)
                # auxiliary layer losses (MoE load-balancing), as in
                # _loss_and_new_state
                for st in new_states:
                    if isinstance(st, dict) and "aux_loss" in st:
                        loss = loss + st["aux_loss"]
                if scaling:
                    loss = loss * scale
                return loss, (new_states, new_carries)

            if remat_policy is not None:
                loss_fn = jax.checkpoint(loss_fn, policy=remat_policy)
            (loss, (new_states, new_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
            if scaling:
                inv = 1.0 / scale
                grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
                loss = loss * inv
            if guarded:
                from deeplearning4j_tpu.train import faults as _faults

                grads = _faults.inject_gradient_faults(grads, iteration)
                finite = _faults.all_finite(grads)
            # NOTE: tBPTT applies one updater step per CHUNK but advances
            # the host iteration once per batch (all chunks of a batch see
            # the same ``iteration``) — the guarded variant keeps that
            # clocking and only folds in the skip, so enabling the policy
            # without faults does not perturb the trajectory
            t = iteration + 1
            new_params, new_opt = _apply_layer_updates(
                layers, params, grads, opt_state, t, iteration, epoch
            )
            # tBPTT truncation is inherent: carries cross chunks only as
            # fresh step inputs (each chunk is its own jit call), so no
            # gradient flows across the boundary (reference semantics)
            score = loss + self._reg_score(params)
            if not guarded:
                return new_params, new_opt, new_states, new_carries, score
            from deeplearning4j_tpu.train import faults as _faults

            if do_skip:
                new_params = _faults.where_tree(finite, new_params, params)
                new_opt = _faults.where_tree(finite, new_opt, opt_state)
                new_states = _faults.where_tree(finite, new_states, state)
                new_carries = _faults.where_tree(finite, new_carries, carries)
            new_fstate = _faults.advance_fault_state(policy, fstate, finite)
            return (new_params, new_opt, new_states, new_fstate, new_carries,
                    score)

        if guarded:
            def step(params, opt_state, state, fstate, carries, features,
                     labels, fmask, lmask, rng, iteration, epoch):
                return _body(params, opt_state, state, fstate, carries,
                             features, labels, fmask, lmask, rng, iteration,
                             epoch)
        else:
            def step(params, opt_state, state, carries, features, labels,
                     fmask, lmask, rng, iteration, epoch):
                return _body(params, opt_state, state, None, carries,
                             features, labels, fmask, lmask, rng, iteration,
                             epoch)

        if not jit:
            return step
        if guarded:
            from deeplearning4j_tpu.train import faults as _faults

            return jax.jit(step, donate_argnums=_faults.guard_donation(0, 1, 2))
        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _init_carries(self, batch: int, dtype=jnp.float32) -> List[Any]:
        carries: List[Any] = []
        for layer in self.layers:
            if isinstance(layer, BaseRecurrentLayer):
                carries.append(layer.init_carry(batch, dtype))
            else:
                carries.append(None)
        return carries

    def _fit_tbptt_batch(self, ds: DataSet):
        """Chunked truncated-BPTT over the time axis (reference
        ``doTruncatedBPTT``, ``MultiLayerNetwork.java:1315-1317``): carries
        thread across chunks, gradients stop at chunk boundaries."""
        step = self._get_jit("tbptt", self._make_tbptt_step)
        T = ds.features.shape[1]
        L = self.conf.tbptt_fwd_length
        if ds.labels is not None and ds.labels.ndim != 3:
            raise ValueError(
                "tBPTT requires per-timestep labels (batch, time, nOut); got "
                f"labels shape {ds.labels.shape}. For per-sequence labels use "
                "standard backprop (the reference has the same requirement)."
            )
        carries = self._init_carries(ds.features.shape[0])
        policy = self._active_fault_policy()
        for lo in range(0, T, L):
            hi = min(lo + L, T)
            f = jnp.asarray(ds.features[:, lo:hi])
            l = None if ds.labels is None else jnp.asarray(ds.labels[:, lo:hi])
            fm = None if ds.features_mask is None else jnp.asarray(ds.features_mask[:, lo:hi])
            lm = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask[:, lo:hi])
            if policy is not None:
                fstate = self._ensure_fault_state(policy)
                (self.params_, self.opt_state_, self.state_,
                 self.fault_state_, carries, self.score_) = step(
                    self.params_, self.opt_state_, self.state_, fstate,
                    carries, f, l, fm, lm,
                    self._next_rng(),
                    jnp.asarray(self.iteration, jnp.int32),
                    jnp.asarray(self.epoch, jnp.int32),
                )
            else:
                (self.params_, self.opt_state_, self.state_, carries,
                 self.score_) = step(
                    self.params_, self.opt_state_, self.state_, carries,
                    f, l, fm, lm,
                    self._next_rng(),
                    jnp.asarray(self.iteration, jnp.int32),
                    jnp.asarray(self.epoch, jnp.int32),
                )
        self.iteration += 1
        if policy is not None:
            from deeplearning4j_tpu.train import faults as _faults

            _faults.check_fault_state(policy, self.fault_state_, owner=self)
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration, self.epoch)

    # --------------------------------------------------------------- pretrain
    def pretrain(self, it: DataSetIterator, epochs: int = 1) -> "MultiLayerNetwork":
        """Greedy layer-wise unsupervised pretraining of all pretrain-capable
        layers (reference ``MultiLayerNetwork.pretrain(DataSetIterator)``)."""
        for i, layer in enumerate(self.layers):
            if layer.is_pretrain_layer:
                self.pretrain_layer(i, it, epochs=epochs)
        return self

    def pretrain_layer(self, layer_idx: int, it: DataSetIterator,
                       epochs: int = 1) -> "MultiLayerNetwork":
        """Unsupervised pretraining of one layer (reference
        ``pretrainLayer``): features flow through layers [0, layer_idx) in
        inference mode, then the layer's ``pretrain_loss`` (-ELBO /
        reconstruction error) is minimized over its params only — one jitted
        step per layer."""
        layer = self.layers[layer_idx]
        if not layer.is_pretrain_layer:
            raise ValueError(f"Layer {layer_idx} ({layer}) is not pretrainable")

        def step(layer_params, opt_i, all_params, state, features, rng, iteration, epoch):
            x, _, _, _, _ = self._forward(
                dict_to_list_params(all_params, layer_params, layer_idx),
                state, features, train=False, rng=None, stop_before=layer_idx,
            )

            def loss_fn(p):
                return layer.pretrain_loss(p, x, rng)

            loss, grads = jax.value_and_grad(loss_fn)(layer_params)
            # the shared pipeline applies normalization, regularization,
            # updater AND constraints (a hand-rolled copy here previously
            # skipped constraints)
            (new_p,), (new_o,) = _apply_layer_updates(
                [layer], [layer_params], [grads], [opt_i],
                iteration + 1, iteration, epoch,
            )
            return new_p, new_o, loss

        def dict_to_list_params(all_params, layer_params, idx):
            return [layer_params if j == idx else all_params[j]
                    for j in range(len(all_params))]

        jit_step = self._get_jit(f"pretrain{layer_idx}", lambda: jax.jit(step))
        for _ in range(epochs):
            for ds in it:
                new_p, new_o, loss = jit_step(
                    self.params_[layer_idx], self.opt_state_[layer_idx],
                    self.params_, self.state_, jnp.asarray(ds.features),
                    self._next_rng(),
                    jnp.asarray(self.iteration, jnp.int32),
                    jnp.asarray(self.epoch, jnp.int32),
                )
                self.params_ = [
                    new_p if j == layer_idx else p for j, p in enumerate(self.params_)
                ]
                self.opt_state_ = [
                    new_o if j == layer_idx else o for j, o in enumerate(self.opt_state_)
                ]
                self.score_ = loss
                self.iteration += 1
            it.reset()
        return self

    # -------------------------------------------------------------- inference
    def _make_output_fn(self):
        def run(params, state, x, fmask):
            y, _, _, _, _ = self._forward(params, state, x, train=False, rng=None, fmask=fmask)
            return y

        return jax.jit(run)

    def output(self, x, mask=None) -> np.ndarray:
        """Inference (reference ``output:2031``)."""
        fn = self._get_jit("output", self._make_output_fn)
        y = fn(self.params_, self.state_, jnp.asarray(x),
               None if mask is None else jnp.asarray(mask))
        return np.asarray(y)

    def feed_forward(self, x, train: bool = False) -> List[np.ndarray]:
        """All layer activations (reference ``feedForward``); unjitted
        introspection path (SURVEY.md §7 hard-part 1)."""
        _, _, _, _, acts = self._forward(
            self.params_, self.state_, jnp.asarray(x), train=train,
            rng=self._next_rng() if train else None, collect=True,
        )
        return [np.asarray(a) for a in acts]

    # -------------------------------------------------------------- rnn state
    def rnn_clear_previous_state(self):
        self._rnn_carries = None

    def rnn_get_previous_state(self):
        """Per-layer streaming hidden state, host-side (reference
        ``rnnGetPreviousState``); None before any rnn_time_step."""
        if self._rnn_carries is None:
            return None
        return jax.tree_util.tree_map(np.asarray, self._rnn_carries)

    def rnn_set_previous_state(self, carries) -> None:
        """Restore streaming state captured by ``rnn_get_previous_state``
        (reference ``rnnSetPreviousState``) — e.g. to resume serving
        after a process restart."""
        self._rnn_carries = None if carries is None else \
            jax.tree_util.tree_map(jnp.asarray, carries)

    def rnn_time_step(self, x) -> np.ndarray:
        """Stateful streaming inference (reference ``rnnTimeStep``)."""
        x = jnp.asarray(x)
        squeeze = False
        if x.ndim == 2:  # (b, size) → single step
            x = x[:, None, :]
            squeeze = True
        if self._rnn_carries is None:
            self._rnn_carries = self._init_carries(x.shape[0], x.dtype)

        def run(params, state, x, carries):
            y, _, _, new_carries, _ = self._forward(
                params, state, x, train=False, rng=None, carries=carries
            )
            return y, new_carries

        fn = self._get_jit("rnn_step", lambda: jax.jit(run))
        y, self._rnn_carries = fn(self.params_, self.state_, x, self._rnn_carries)
        y = np.asarray(y)
        return y[:, -1, :] if squeeze else y

    # ------------------------------------------------------------------ score
    def score(self, ds: Optional[DataSet] = None) -> float:
        """Loss incl. regularization terms (reference ``score()``)."""
        if ds is None:
            if self.score_ is None:
                raise ValueError("No score available; fit() first or pass a DataSet")
            return float(self.score_)

        def run(params, state, f, l, fm, lm):
            loss, _ = self._loss_and_new_state(params, state, f, l, fm, lm, None, train=False)
            return loss + self._reg_score(params)

        fn = self._get_jit("score", lambda: jax.jit(run))
        return float(
            fn(self.params_, self.state_, jnp.asarray(ds.features),
               None if ds.labels is None else jnp.asarray(ds.labels),
               None if ds.features_mask is None else jnp.asarray(ds.features_mask),
               None if ds.labels_mask is None else jnp.asarray(ds.labels_mask))
        )

    def compute_gradient_and_score(self, ds: DataSet):
        """Introspection API (reference ``computeGradientAndScore():2360``):
        returns (gradients pytree, score) without updating params."""

        def run(params, state, f, l, fm, lm, rng):
            def loss_fn(p):
                loss, _ = self._loss_and_new_state(p, state, f, l, fm, lm, rng, train=True)
                return loss

            loss, grads = jax.value_and_grad(loss_fn)(params)
            return grads, loss + self._reg_score(params)

        fn = self._get_jit("grad_score", lambda: jax.jit(run))
        grads, score = fn(
            self.params_, self.state_, jnp.asarray(ds.features),
            None if ds.labels is None else jnp.asarray(ds.labels),
            None if ds.features_mask is None else jnp.asarray(ds.features_mask),
            None if ds.labels_mask is None else jnp.asarray(ds.labels_mask),
            self._next_rng(),
        )
        return grads, float(score)

    # ------------------------------------------------------------- evaluation
    def evaluate(self, it: Union[DataSetIterator, DataSet], top_n: int = 1):
        """(reference ``evaluate(DataSetIterator)`` and the topN overload)"""
        from deeplearning4j_tpu.evaluation import Evaluation

        return self._evaluate_with(it, Evaluation(top_n=top_n))

    def predict(self, x) -> np.ndarray:
        """Predicted class index per example (reference ``predict``);
        time-distributed outputs return (b, T) indices."""
        return np.argmax(self.output(x), axis=-1)

    def f1_score(self, ds: Union[DataSet, DataSetIterator]) -> float:
        """Micro-averaged F1 (reference ``f1Score(DataSet)``)."""
        return float(self.evaluate(ds).f1())

    def score_examples(self, ds: DataSet,
                       add_regularization_terms: bool = True) -> np.ndarray:
        """Per-example loss (reference ``scoreExamples``): the unreduced
        output-layer loss, optionally plus the (shared) l1/l2 penalty."""

        def run(params, state, f, l, fm, lm):
            n = len(self.layers)
            x, mask, _, _, _ = self._forward(
                params, state, f, train=False, rng=None, fmask=fm,
                stop_before=n - 1)
            if self._compute_dtype is not None:
                x = x.astype(jnp.float32)
            out_layer = self._output_layer()
            label_mask = lm if lm is not None else mask
            kw = {"state": state[-1]} if isinstance(
                out_layer, CenterLossOutputLayer) else {}
            per_ex = out_layer.compute_score(params[-1], x, l, label_mask,
                                             **kw)
            if add_regularization_terms:
                per_ex = per_ex + self._reg_score(params)
            return per_ex

        fn = self._get_jit(
            f"score_examples_reg{int(add_regularization_terms)}",
            lambda: jax.jit(run))
        return np.asarray(fn(
            self.params_, self.state_, jnp.asarray(ds.features),
            None if ds.labels is None else jnp.asarray(ds.labels),
            None if ds.features_mask is None else jnp.asarray(ds.features_mask),
            None if ds.labels_mask is None else jnp.asarray(ds.labels_mask),
        ))

    def layer_size(self, layer_idx: int) -> int:
        """Output size of layer ``layer_idx`` (reference ``layerSize``:
        nOut for dense/recurrent layers, channels for convolutional,
        0 where undefined)."""
        types = self.conf.layer_types()
        out = self.layers[layer_idx].get_output_type(types[layer_idx])
        if out.kind in ("feedforward", "recurrent"):
            return int(out.size)
        if out.kind == "convolutional":
            return int(out.channels)
        return 0

    def to_computation_graph(self):
        """Convert to an equivalent ComputationGraph (reference
        ``toComputationGraph``): layers become a linear vertex chain
        ("layer_0" → … → "layer_{n-1}" from input "input"), preprocessors
        ride their layer's vertex, params/state/updater-state are copied
        over, so outputs match exactly."""
        import copy

        from deeplearning4j_tpu.nn.conf.graph_builder import GraphBuilder
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        gb = GraphBuilder(copy.deepcopy(self.conf.global_conf))
        gb.add_inputs("input")
        prev = "input"
        for i, layer in enumerate(self.layers):
            name = f"layer_{i}"
            gb.add_layer(name, copy.deepcopy(layer), prev,
                         preprocessor=copy.deepcopy(
                             self.conf.preprocessors.get(i)))
            prev = name
        gb.set_outputs(prev)
        if self.conf.input_type is not None:
            gb.set_input_types(self.conf.input_type)
        cg = ComputationGraph(gb.build(), copy_conf=False)
        if self.params_ is not None:
            cg.init()
            for i in range(len(self.layers)):
                name = f"layer_{i}"
                cg.params_[name] = dict(self.params_[i])
                cg.state_[name] = dict(self.state_[i])
                cg.opt_state_[name] = copy.deepcopy(self.opt_state_[i])
            cg.iteration, cg.epoch = self.iteration, self.epoch
        return cg

    def set_learning_rate(self, lr: float) -> None:
        """Set the learning rate on every layer's updater (reference
        ``setLearningRate``); takes effect on the next jitted step (the
        step closes over the updater, so the compiled fn is invalidated)."""
        from deeplearning4j_tpu.schedules import as_schedule

        for layer in self.layers:
            upd = layer.updater
            if upd is not None and getattr(upd, "has_learning_rate", False):
                upd.learning_rate = as_schedule(float(lr))
        # every cached step closed over the old schedule (train, tbptt,
        # pretrain{i}, ...) — drop them all; they recompile on demand
        self._jit_cache.clear()

    setLearningRate = set_learning_rate

    def _evaluate_with(self, it, ev):
        """Shared drive loop for the evaluate-family helpers."""
        if isinstance(it, DataSet):
            it = ListDataSetIterator(it, 256)
        for ds in it:
            out = self.output(ds.features, mask=ds.features_mask)
            ev.eval(ds.labels, out, mask=ds.labels_mask)
        it.reset()
        return ev

    def evaluate_roc(self, it, threshold_steps: int = 0):
        """Binary ROC over the iterator (reference ``evaluateROC``)."""
        from deeplearning4j_tpu.evaluation import ROC

        return self._evaluate_with(it, ROC(threshold_steps))

    def evaluate_roc_multi_class(self, it, threshold_steps: int = 0):
        """One-vs-all ROC per class (reference ``evaluateROCMultiClass``)."""
        from deeplearning4j_tpu.evaluation import ROCMultiClass

        return self._evaluate_with(it, ROCMultiClass(threshold_steps))

    def evaluate_regression(self, it: Union[DataSetIterator, DataSet]):
        from deeplearning4j_tpu.evaluation import RegressionEvaluation

        return self._evaluate_with(it, RegressionEvaluation())

    # ------------------------------------------------------- params utilities
    def num_params(self) -> int:
        assert self.params_ is not None
        return int(sum(int(np.prod(a.shape)) for p in self.params_ for a in p.values()))

    def summary(self) -> str:
        """Layer table — name, input→output type, #params (reference
        ``MultiLayerNetwork.summary():3230``)."""
        types = self.conf.layer_types()
        out_types = [l.get_output_type(t)
                     for l, t in zip(self.layers, types)]
        rows = [("idx", "layer", "input", "output", "params")]
        total = 0
        for i, layer in enumerate(self.layers):
            n = (int(sum(int(np.prod(a.shape))
                         for a in self.params_[i].values()))
                 if self.params_ is not None else 0)
            total += n
            rows.append((str(i), type(layer).__name__, str(types[i]),
                         str(out_types[i]), f"{n:,}"))
        widths = [max(len(r[c]) for r in rows) for c in range(5)]
        lines = ["  ".join(r[c].ljust(widths[c]) for c in range(5))
                 for r in rows]
        lines.insert(1, "-" * (sum(widths) + 8))
        lines.append(f"Total parameters: {total:,}")
        return "\n".join(lines)

    def params_flat(self) -> np.ndarray:
        """Single flattened parameter vector (reference ``params()``; order:
        layer index asc, param name sorted asc — deterministic for
        checkpoint format)."""
        assert self.params_ is not None
        chunks = []
        for p in self.params_:
            for name in sorted(p):
                chunks.append(np.asarray(p[name], np.float32).reshape(-1))
        if not chunks:
            return np.zeros((0,), np.float32)
        return np.concatenate(chunks)

    def set_params_flat(self, vec: np.ndarray) -> None:
        assert self.params_ is not None
        vec = np.asarray(vec, np.float32)
        expected = self.num_params()
        if vec.size != expected:
            raise ValueError(f"Param vector length {vec.size} != model size {expected}")
        off = 0
        new_params = []
        for p in self.params_:
            np_i = {}
            for name in sorted(p):
                n = int(np.prod(p[name].shape))
                np_i[name] = jnp.asarray(
                    vec[off : off + n].reshape(p[name].shape), p[name].dtype
                )
                off += n
            new_params.append(np_i)
        if off != vec.size:
            raise ValueError(f"Param vector length {vec.size} != model size {off}")
        self.params_ = new_params

    def opt_state_flat(self) -> np.ndarray:
        """Flattened updater state (order: layer, param name, slot name)."""
        assert self.opt_state_ is not None
        chunks = []
        for o in self.opt_state_:
            for name in sorted(o):
                slots = o[name]
                for slot in sorted(slots):
                    chunks.append(np.asarray(slots[slot], np.float32).reshape(-1))
        if not chunks:
            return np.zeros((0,), np.float32)
        return np.concatenate(chunks)

    def set_opt_state_flat(self, vec: np.ndarray) -> None:
        assert self.opt_state_ is not None
        vec = np.asarray(vec, np.float32)
        off = 0
        new_opt = []
        for o in self.opt_state_:
            no_i = {}
            for name in sorted(o):
                slots = {}
                for slot in sorted(o[name]):
                    arr = o[name][slot]
                    n = int(np.prod(arr.shape))
                    slots[slot] = jnp.asarray(vec[off : off + n].reshape(arr.shape), arr.dtype)
                    off += n
                no_i[name] = slots
            new_opt.append(no_i)
        self.opt_state_ = new_opt

    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    def add_listeners(self, *listeners) -> None:
        self.listeners.extend(listeners)

    def clone(self) -> "MultiLayerNetwork":
        """Deep copy via config JSON + param copy (reference ``clone()``)."""
        conf = MultiLayerConfiguration.from_json(self.conf.to_json())
        net = MultiLayerNetwork(conf, copy_conf=False)
        if self.params_ is not None:
            # deep copy, no init(): the source's train step donates its
            # buffers to XLA, so shared arrays would be deleted under it
            net.params_ = jax.tree_util.tree_map(jnp.copy, self.params_)
            net.state_ = jax.tree_util.tree_map(jnp.copy, self.state_)
            net.opt_state_ = jax.tree_util.tree_map(jnp.copy, self.opt_state_)
            net.iteration = self.iteration
            net.epoch = self.epoch
        return net
