"""ComputationGraph: DAG network runtime.

Reference: ``nn/graph/ComputationGraph.java`` (3,904 LoC) — topological
sort (``:1216``), multi-input/multi-output fit (``fit(DataSet):862``,
``fit(MultiDataSetIterator):1015``), ``computeGradientAndScore():1321``,
``output:1759``, ``feedForward:1409-1489``.

TPU-native design: like MultiLayerNetwork, the whole train step (forward
over the topological order, backward, updater math, constraints) is ONE
jit-compiled XLA program with donated buffers. The vertex walk is traced —
the DAG becomes straight-line XLA HLO, so vertex dispatch overhead is zero
at run time and XLA fuses across vertex boundaries.

State layout (keyed by vertex name, only LayerVertex entries have params):
- ``self.params_``:    dict name → dict pname → array
- ``self.state_``:     dict name → dict (BN stats etc.)
- ``self.opt_state_``: dict name → dict pname → updater slots
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.data.iterators import (
    DataSetIterator,
    ListDataSetIterator,
    MultiDataSetIterator,
)
from deeplearning4j_tpu.nn.conf.graph_builder import (
    ComputationGraphConfiguration,
    LayerVertex,
)
from deeplearning4j_tpu.nn.conf.graph_vertices import (
    DuplicateToTimeSeriesVertex,
    LastTimeStepVertex,
    ReverseTimeSeriesVertex,
)
from deeplearning4j_tpu.nn.conf.layers.base import (
    apply_input_dropout,
    apply_weight_noise,
)
from deeplearning4j_tpu.nn.conf.layers.special import CenterLossOutputLayer
from deeplearning4j_tpu.nn.multilayer import (
    MultiLayerNetwork,
    _apply_layer_updates,
    _cast_layer_params_for_compute,
    _dtype_of,
    _resolve_remat_policy,
)
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


def _as_multi(ds: Union[DataSet, MultiDataSet]) -> MultiDataSet:
    if isinstance(ds, MultiDataSet):
        return ds
    return MultiDataSet(
        [ds.features], [] if ds.labels is None else [ds.labels],
        [ds.features_mask], [ds.labels_mask],
    )


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration, *,
                 copy_conf: bool = True):
        import copy

        # private conf copy — see MultiLayerNetwork.__init__: keeps
        # set_learning_rate & co from mutating sibling networks built
        # from the same configuration object; copy_conf=False for
        # callers handing over a conf nothing else holds
        if copy_conf:
            conf = copy.deepcopy(conf)
        self.conf = conf
        self.topo = conf.topological_order
        # deterministic list of layer-vertex names (topo order) — the
        # canonical ordering for flattened params / updates
        self.layer_names: List[str] = [
            n for n in self.topo if isinstance(conf.vertices[n], LayerVertex)
        ]
        self.params_: Optional[Dict[str, Dict[str, Array]]] = None
        self.state_: Optional[Dict[str, Dict[str, Array]]] = None
        self.opt_state_: Optional[Dict[str, Any]] = None
        self.iteration = 0
        self.epoch = 0
        self.score_: Optional[Array] = None
        # fault-tolerance carry (train/faults.py), as on MultiLayerNetwork
        self.fault_state_: Optional[Dict[str, Array]] = None
        self.listeners: List[Any] = []
        self._rng = jax.random.PRNGKey(conf.global_conf.seed)
        self._jit_cache: Dict[str, Any] = {}
        cd = getattr(conf.global_conf, "compute_dtype", None)
        self._compute_dtype = None if cd is None else _dtype_of(cd)
        self._output_layers()  # fail fast with a clear message on misconfig

    # ---------------------------------------------------------- fault policy
    # (same surface as MultiLayerNetwork; both model types feed the same
    # data-parallel runtimes)
    _active_fault_policy = MultiLayerNetwork._active_fault_policy
    _ensure_fault_state = MultiLayerNetwork._ensure_fault_state
    set_fault_policy = MultiLayerNetwork.set_fault_policy
    bad_step_count = MultiLayerNetwork.bad_step_count
    loss_scale = MultiLayerNetwork.loss_scale

    def _cast_for_compute(self, params):
        cd = self._compute_dtype
        if cd is None:
            return params
        out = dict(params)
        for name in self.layer_names:
            layer = self._layer(name)
            out[name] = _cast_layer_params_for_compute(
                layer, params[name], cd, is_output=layer.is_output_layer
            )
        return out

    def _layer(self, name: str):
        return self.conf.vertices[name].layer

    # ------------------------------------------------------------------ init
    def init(self, rng: Optional[Array] = None) -> "ComputationGraph":
        if self.conf.input_types is None:
            raise ValueError("Configuration needs set_input_types(...) before init()")
        rng = rng if rng is not None else jax.random.PRNGKey(self.conf.global_conf.seed)
        dtype = _dtype_of(self.conf.global_conf.dtype)
        lt = self.conf.layer_input_types()
        params: Dict[str, Dict[str, Array]] = {}
        state: Dict[str, Dict[str, Array]] = {}
        opt_state: Dict[str, Any] = {}
        keys = jax.random.split(rng, max(len(self.layer_names), 1))
        for i, name in enumerate(self.layer_names):
            layer = self._layer(name)
            p = layer.init_params(keys[i], lt[name], dtype)
            s = layer.init_layer_state(lt[name], dtype)
            params[name] = p
            state[name] = s
            upd = layer.updater if layer.updater is not None else NoOp()
            opt_state[name] = {pn: upd.init_state(arr) for pn, arr in p.items()}
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
        inputs: Sequence[Array],
        *,
        train: bool,
        rng: Optional[Array],
        fmasks: Optional[Sequence[Optional[Array]]] = None,
        collect: bool = False,
        carries: Optional[Dict[str, Any]] = None,
        stop_before_vertex: Optional[str] = None,
    ):
        """Pure forward walk over the topological order.

        Returns (activations dict, masks dict, output-layer-inputs dict,
        new_state dict[, new_carries when ``carries`` is given]).
        ``output-layer-inputs`` holds, for each LayerVertex whose layer is
        an output layer, the activation INTO that layer
        (post-preprocessor) — needed by compute_score, mirroring the
        reference's "forward to N-1 then score" structure
        (``ComputationGraph.java:1321``). ``carries`` maps recurrent
        layer-vertex names to hidden state threaded across tBPTT chunks /
        rnnTimeStep calls (reference ``rnnActivateUsingStoredState``).
        """
        from deeplearning4j_tpu.nn.conf.layers.recurrent import BaseRecurrentLayer

        conf = self.conf
        new_carries: Dict[str, Any] = {}
        if self._compute_dtype is not None:
            params = self._cast_for_compute(params)
            inputs = [
                jnp.asarray(x).astype(self._compute_dtype)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x
                for x in inputs
            ]
        acts: Dict[str, Array] = dict(zip(conf.network_inputs, inputs))
        masks: Dict[str, Optional[Array]] = {n: None for n in conf.network_inputs}
        if fmasks is not None:
            for n, m in zip(conf.network_inputs, fmasks):
                masks[n] = m
        out_inputs: Dict[str, Tuple[Array, Optional[Array]]] = {}
        new_state: Dict[str, Dict[str, Array]] = {}
        n_l = max(len(self.layer_names), 1)
        rngs = dict(zip(self.layer_names,
                        jax.random.split(rng, n_l))) if rng is not None else {}
        for name in self.topo:
            if stop_before_vertex is not None and name == stop_before_vertex:
                break
            v = conf.vertices[name]
            srcs = conf.vertex_inputs[name]
            in_acts = [acts[s] for s in srcs]
            in_masks = [masks[s] for s in srcs]
            if isinstance(v, LayerVertex):
                layer = v.layer
                x, m = in_acts[0], in_masks[0]
                if v.preprocessor is not None:
                    x = v.preprocessor.pre_process(x, m)
                    m = v.preprocessor.feed_forward_mask(m)
                r = rngs.get(name)
                x = apply_input_dropout(layer, x, train, r)
                if layer.is_output_layer:
                    out_inputs[name] = (x, m)
                # output layers: weight noise applies in the SCORE path
                # (compute_score) only — noising here too would draw a
                # second, different mask for the same step
                p_n = params.get(name, {}) if layer.is_output_layer else \
                    apply_weight_noise(layer, params.get(name, {}), train, r)
                if (
                    carries is not None
                    and isinstance(layer, BaseRecurrentLayer)
                    and carries.get(name) is not None
                ):
                    y, c = layer.apply_with_carry(
                        p_n, x, carries[name],
                        mask=m, train=train, rng=r,
                    )
                    new_carries[name] = c
                    st = state.get(name, {})
                else:
                    y, st = layer.apply(
                        p_n, x, state=state.get(name, {}),
                        train=train, rng=r, mask=m,
                    )
                new_state[name] = st if st is not None else {}
                acts[name] = y
                if layer.is_recurrent and m is not None:
                    masks[name] = m
                elif y.ndim == 2 and m is not None and m.ndim > 1:
                    masks[name] = None  # mask consumed (pooling/last-step)
                else:
                    masks[name] = m
            else:
                # rnn vertices that name a mask source resolve it here
                if isinstance(v, (LastTimeStepVertex, ReverseTimeSeriesVertex)) and v.mask_input:
                    in_masks = [masks.get(v.mask_input)] + in_masks[1:]
                acts[name] = v.apply(in_acts, in_masks, train=train, rng=None)
                masks[name] = v.feed_forward_mask(in_masks)
        if carries is not None:
            return acts, masks, out_inputs, new_state, new_carries
        return acts, masks, out_inputs, new_state

    def _output_layers(self) -> List[str]:
        outs = []
        for name in self.conf.network_outputs:
            v = self.conf.vertices[name]
            if not (isinstance(v, LayerVertex) and v.layer.is_output_layer):
                raise ValueError(f"Network output '{name}' is not an output layer")
            outs.append(name)
        return outs

    # ---------------------------------------------------------------- scoring
    def _loss_and_new_state(self, params, state, features, labels, fmasks, lmasks,
                            rng, train=True):
        _, _, out_inputs, new_state = self._forward(
            params, state, features, train=train, rng=rng, fmasks=fmasks
        )
        loss = jnp.asarray(0.0, jnp.float32)
        for i, name in enumerate(self.conf.network_outputs):
            layer = self._layer(name)
            x, m = out_inputs[name]
            if self._compute_dtype is not None:
                x = x.astype(jnp.float32)  # loss/softmax in full precision
            lmask = None
            if lmasks is not None and i < len(lmasks):
                lmask = lmasks[i]
            if lmask is None:
                lmask = m
            # output-layer weight noise (the score path, not apply());
            # fold in the output INDEX — deterministic across processes
            # (string hash() is PYTHONHASHSEED-randomized)
            p_out = apply_weight_noise(
                layer, params[name], train and rng is not None,
                jax.random.fold_in(rng, i) if rng is not None else None,
            )
            if isinstance(layer, CenterLossOutputLayer):
                per_ex = layer.compute_score(p_out, x, labels[i], lmask,
                                             state=state[name])
                if train:
                    new_state[name] = layer.update_centers(new_state[name], x, labels[i])
            else:
                per_ex = layer.compute_score(p_out, x, labels[i], lmask)
            loss = loss + jnp.mean(per_ex)
        # auxiliary layer losses (MoE load-balancing) ride the state pytree
        for st in new_state.values():
            if isinstance(st, dict) and "aux_loss" in st:
                loss = loss + st["aux_loss"]
        return loss, new_state

    def _reg_score(self, params):
        s = jnp.asarray(0.0, jnp.float32)
        for name in self.layer_names:
            reg = self._layer(name).regularization
            if reg is None:
                continue
            for pn, arr in params[name].items():
                s = s + reg.score_term(pn, arr)
        return s

    # ------------------------------------------------------------- train step
    def train_step_fn(self, telemetry=None):
        """Raw (unjitted) pure train step for the data-parallel wrapper.
        ``telemetry`` (obs/telemetry.TelemetryConf) appends a per-step
        in-graph telemetry dict to the outputs."""
        return self._make_train_step(jit=False, telemetry=telemetry)

    def _make_train_step(self, jit: bool = True, telemetry=None):
        names = self.layer_names
        layers = [self._layer(n) for n in names]

        remat_policy = _resolve_remat_policy(
            getattr(self.conf.global_conf, "remat_policy", None)
        )
        policy = self._active_fault_policy()
        if telemetry is not None:
            from deeplearning4j_tpu.obs import telemetry as _obs_telemetry

        def _jit(fn):
            from deeplearning4j_tpu.train import faults as _faults

            # telemetry's extra reads are plain dataflow; the
            # guard_donation CPU gate stays scoped to the guarded steps'
            # where-select aliasing pattern (the observed miscompile)
            donate = (_faults.guard_donation(0, 1, 2)
                      if policy is not None else (0, 1, 2))
            return jax.jit(
                _trace.count_retraces(f"{type(self).__name__}.train_step",
                                      fn),
                donate_argnums=donate)

        if policy is None:
            def step(params, opt_state, state, features, labels, fmasks, lmasks, rng,
                     iteration, epoch):
                def loss_fn(p):
                    loss, new_state = self._loss_and_new_state(
                        p, state, features, labels, fmasks, lmasks, rng, train=True
                    )
                    return loss, new_state

                if remat_policy is not None:
                    loss_fn = jax.checkpoint(loss_fn, policy=remat_policy)
                (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
                t = iteration + 1
                p_list = [params[n] for n in names]
                g_list = [grads[n] for n in names]
                o_list = [opt_state[n] for n in names]
                np_list, no_list = _apply_layer_updates(
                    layers, p_list, g_list, o_list, t, iteration, epoch
                )
                new_params = dict(zip(names, np_list))
                new_opt = dict(zip(names, no_list))
                score = loss + self._reg_score(params)
                if telemetry is not None:
                    telem = _obs_telemetry.step_telemetry(
                        telemetry, grads, params, new_params)
                    return new_params, new_opt, new_state, score, telem
                return new_params, new_opt, new_state, score

            return _jit(step) if jit else step

        # guarded variant — see MultiLayerNetwork._make_train_step for the
        # mechanism (loss scaling, global verdict, where-skip, good_count
        # updater clock)
        from deeplearning4j_tpu.train import faults as _faults

        scaling = policy.scaling_active(self._compute_dtype)
        do_skip = policy.skip_nonfinite or scaling

        def gstep(params, opt_state, state, fstate, features, labels, fmasks,
                  lmasks, rng, iteration, epoch):
            scale = fstate["loss_scale"] if scaling else None

            def loss_fn(p):
                loss, new_state = self._loss_and_new_state(
                    p, state, features, labels, fmasks, lmasks, rng, train=True
                )
                if scaling:
                    loss = loss * scale
                return loss, new_state

            if remat_policy is not None:
                loss_fn = jax.checkpoint(loss_fn, policy=remat_policy)
            (loss, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if scaling:
                inv = 1.0 / scale
                grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
                loss = loss * inv
            grads = _faults.inject_gradient_faults(grads, iteration)
            finite = _faults.all_finite(grads)
            t_good = fstate["good_count"]
            p_list = [params[n] for n in names]
            g_list = [grads[n] for n in names]
            o_list = [opt_state[n] for n in names]
            np_list, no_list = _apply_layer_updates(
                layers, p_list, g_list, o_list, t_good + 1, t_good, epoch
            )
            new_params = dict(zip(names, np_list))
            new_opt = dict(zip(names, no_list))
            if do_skip:
                new_params = _faults.where_tree(finite, new_params, params)
                new_opt = _faults.where_tree(finite, new_opt, opt_state)
                new_state = _faults.where_tree(finite, new_state, state)
            new_fstate = _faults.advance_fault_state(policy, fstate, finite)
            score = loss + self._reg_score(params)
            if telemetry is not None:
                telem = _obs_telemetry.step_telemetry(
                    telemetry, grads, params, new_params, fstate=new_fstate,
                    scale=scale)
                return (new_params, new_opt, new_state, new_fstate, score,
                        telem)
            return new_params, new_opt, new_state, new_fstate, score

        return _jit(gstep) if jit else gstep

    def _get_jit(self, key, maker):
        if key not in self._jit_cache:
            self._jit_cache[key] = maker()
        return self._jit_cache[key]

    # ------------------------------------------------------------------- fit
    def fit(
        self,
        data: Union[DataSet, MultiDataSet, DataSetIterator, MultiDataSetIterator],
        epochs: int = 1,
        batch_size: int = 32,
    ) -> "ComputationGraph":
        if isinstance(data, DataSet):
            data = ListDataSetIterator(data, batch_size)
        if isinstance(data, MultiDataSet):
            data = MultiDataSetIterator.from_list([data])
        from deeplearning4j_tpu.train.listeners import dispatch_fit_end
        try:
            for _ in range(epochs):
                self._fit_one_epoch(data)
        finally:
            # close listener-held resources (open profiler trace windows)
            # even when an epoch raised
            dispatch_fit_end(self.listeners, self)
        return self

    @staticmethod
    def _multi_compat_key(mds):
        """MultiDataSet analog of BatchBundle.compat_key: shapes/dtypes/
        mask presence per slot must match for batches to share a bundle."""
        def sig(a):
            return None if a is None else (tuple(a.shape), str(a.dtype))

        return (tuple(sig(f) for f in mds.features),
                tuple(sig(l) for l in mds.labels),
                tuple(sig(m) for m in mds.features_masks),
                tuple(sig(m) for m in mds.labels_masks))

    def _fit_one_epoch(self, it):
        from deeplearning4j_tpu.train import pipeline as _pipeline

        for lst in self.listeners:
            if hasattr(lst, "on_epoch_start"):
                lst.on_epoch_start(self)
        k = _pipeline.resolve_steps_per_call(self)
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
        use_tbptt = getattr(self.conf, "backprop_type", "standard") == "tbptt"
        stream = (_as_multi(ds) for ds in it)
        if k > 1:
            from deeplearning4j_tpu.data.iterators import iter_grouped

            stream = iter_grouped(stream, k, self._multi_compat_key)
        for item in _trace.each_next(_ITERATE, stream):
            if isinstance(item, list):
                self._fit_bundle(bstep, item, tconf)
            elif use_tbptt and item.features[0].ndim == 3:
                self._fit_tbptt_batch(item)
            else:
                self._fit_batch(step, item, tconf)
        it.reset()
        self.epoch += 1
        for lst in self.listeners:
            if hasattr(lst, "on_epoch_end"):
                lst.on_epoch_end(self)

    def _next_rng(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    def _make_introspect_fn(self):
        """(vertex-activation dict, gradients) for one batch — listener
        introspection (SURVEY §7 hard-part 1); same rng as the train step
        so the reported values match the step bit-for-bit. Output-vertex
        activations are recomputed with the score path's weight-noise key
        (fold_in(rng, output_index)) so they reflect the params the
        step's loss actually used."""

        def run(params, state, feats, labels, fmasks, lmasks, rng):
            acts, _, out_inputs, _ = self._forward(
                params, state, feats, train=True, rng=rng, fmasks=fmasks)
            for i, name in enumerate(self.conf.network_outputs):
                layer = self._layer(name)
                x, m = out_inputs[name]
                if self._compute_dtype is not None:
                    x = x.astype(jnp.float32)
                k = jax.random.fold_in(rng, i)
                p_out = apply_weight_noise(layer, params[name], True, k)
                y, _ = layer.apply(p_out, x, state=state[name], train=True,
                                   rng=k, mask=m)
                acts = dict(acts)
                acts[name] = y

            def loss_fn(p):
                loss, _ = self._loss_and_new_state(
                    p, state, feats, labels, fmasks, lmasks, rng, train=True)
                return loss

            grads = jax.grad(loss_fn)(params)
            return acts, grads

        return jax.jit(run)

    def _run_introspection(self, feats, labels, fmasks, lmasks, rng):
        from deeplearning4j_tpu.train.listeners import _hook_recipients

        it_next = self.iteration + 1
        fwd_to = _hook_recipients(self.listeners, "on_forward_pass", it_next)
        grad_to = _hook_recipients(self.listeners, "on_gradient_calculation",
                                   it_next)
        if not (fwd_to or grad_to):
            return
        fn = self._get_jit("introspect", self._make_introspect_fn)
        acts, grads = fn(self.params_, self.state_, feats, labels,
                         fmasks, lmasks, rng)
        if fwd_to:
            acts_np = {k: np.asarray(v) for k, v in acts.items()}
            for lst in fwd_to:
                lst.on_forward_pass(self, acts_np)
        if grad_to:
            grads_np = jax.tree_util.tree_map(np.asarray, grads)
            for lst in grad_to:
                lst.on_gradient_calculation(self, grads_np)

    def _fit_batch(self, step, mds: MultiDataSet, tconf=None):
        from deeplearning4j_tpu.train.listeners import _hook_recipients

        _trace.set_cause(self.iteration)
        with _PUT_BATCH:
            feats = tuple(jnp.asarray(f) for f in mds.features)
            labels = tuple(jnp.asarray(l) for l in mds.labels)
            fmasks = tuple(None if m is None else jnp.asarray(m)
                           for m in mds.features_masks)
            lmasks = tuple(None if m is None else jnp.asarray(m)
                           for m in mds.labels_masks)
        with _DISPATCH:
            rng = self._next_rng()
            self._run_introspection(feats, labels, fmasks, lmasks, rng)
            policy = self._active_fault_policy()
            telem = None
            with _trace.step_span("train", self.iteration):
                if policy is not None:
                    fstate = self._ensure_fault_state(policy)
                    out = step(
                        self.params_, self.opt_state_, self.state_, fstate,
                        feats, labels, fmasks, lmasks, rng,
                        jnp.asarray(self.iteration, jnp.int32),
                        jnp.asarray(self.epoch, jnp.int32),
                    )
                    if tconf is not None:
                        *out, telem = out
                    (self.params_, self.opt_state_, self.state_,
                     self.fault_state_, self.score_) = out
                else:
                    out = step(
                        self.params_, self.opt_state_, self.state_, feats,
                        labels, fmasks, lmasks, rng,
                        jnp.asarray(self.iteration, jnp.int32),
                        jnp.asarray(self.epoch, jnp.int32),
                    )
                    if tconf is not None:
                        *out, telem = out
                    (self.params_, self.opt_state_, self.state_,
                     self.score_) = out
        it0 = self.iteration
        self.iteration += 1
        self.last_batch_size = int(feats[0].shape[0])
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

    def _fit_bundle(self, bstep, group, tconf=None):
        """K optimizer steps in one dispatch (train/pipeline.py): per-slot
        arrays of the K MultiDataSets stack on a new leading axis and the
        bundled lax.scan step consumes them; iteration and the fault-state
        carry advance in-graph (stacked telemetry rides along when
        ``tconf`` is set)."""
        from deeplearning4j_tpu.train import faults as _faults
        from deeplearning4j_tpu.train import pipeline as _pipeline

        k = len(group)

        def stk(slot_arrays):
            if slot_arrays[0] is None:
                return None
            return jnp.stack([jnp.asarray(a) for a in slot_arrays])

        feats = tuple(stk([m.features[i] for m in group])
                      for i in range(len(group[0].features)))
        labels = tuple(stk([m.labels[i] for m in group])
                       for i in range(len(group[0].labels)))
        fmasks = tuple(stk([m.features_masks[i] for m in group])
                       for i in range(len(group[0].features_masks)))
        lmasks = tuple(stk([m.labels_masks[i] for m in group])
                       for i in range(len(group[0].labels_masks)))
        rngs = jnp.stack([self._next_rng() for _ in range(k)])
        policy = self._active_fault_policy()
        it0 = self.iteration
        telem = None

        with _trace.step_span("train_bundle", it0):
            if policy is not None:
                fstate = self._ensure_fault_state(policy)
                out = bstep(
                    self.params_, self.opt_state_, self.state_, fstate,
                    feats, labels, fmasks, lmasks, rngs,
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
                    feats, labels, fmasks, lmasks, rngs,
                    jnp.asarray(it0, jnp.int32),
                    jnp.asarray(self.epoch, jnp.int32),
                )
                if tconf is not None:
                    *out, telem = out
                self.params_, self.opt_state_, self.state_, scores = out
        self.iteration += k
        self.score_ = scores[-1]
        self.last_batch_size = int(feats[0].shape[1])
        if policy is not None:
            _faults.check_fault_state(policy, self.fault_state_, owner=self)
        _pipeline.dispatch_bundle_listeners(self, it0, self.epoch, scores,
                                            telem=telem)

    # --------------------------------------------------------------- pretrain
    def pretrain(self, it, epochs: int = 1) -> "ComputationGraph":
        """Greedy unsupervised pretraining of every pretrain-capable layer
        vertex (reference ``ComputationGraph.pretrain``)."""
        for name in self.layer_names:
            if self._layer(name).is_pretrain_layer:
                self.pretrain_layer(name, it, epochs=epochs)
        return self

    def pretrain_layer(self, name: str, it, epochs: int = 1) -> "ComputationGraph":
        """Unsupervised pretraining of one layer vertex (reference
        ``ComputationGraph.pretrainLayer``): the DAG runs in inference
        mode up to the vertex's input, then the layer's ``pretrain_loss``
        (-ELBO / reconstruction error) is minimized over its params only."""
        layer = self._layer(name)
        if not layer.is_pretrain_layer:
            raise ValueError(f"Layer vertex '{name}' is not pretrainable")
        v = self.conf.vertices[name]
        src = self.conf.vertex_inputs[name][0]

        def step(layer_params, opt_n, all_params, state, features, rng,
                 iteration, epoch):
            params = dict(all_params)
            params[name] = layer_params
            # the walk stops at the pretrained vertex: downstream vertices
            # are irrelevant to the unsupervised objective
            acts, masks, _, _ = self._forward(
                params, state, features, train=False, rng=None,
                stop_before_vertex=name,
            )
            x = acts[src]
            if v.preprocessor is not None:
                x = v.preprocessor.pre_process(x, masks.get(src))

            loss, grads = jax.value_and_grad(
                lambda p: layer.pretrain_loss(p, x, rng)
            )(layer_params)
            # shared pipeline: normalization, regularization, updater AND
            # constraints
            (new_p,), (new_o,) = _apply_layer_updates(
                [layer], [layer_params], [grads], [opt_n],
                iteration + 1, iteration, epoch,
            )
            return new_p, new_o, loss

        jit_step = self._get_jit(f"pretrain_{name}", lambda: jax.jit(step))
        for _ in range(epochs):
            for ds in it:
                mds = _as_multi(ds)
                new_p, new_o, loss = jit_step(
                    self.params_[name], self.opt_state_[name],
                    self.params_, self.state_,
                    tuple(jnp.asarray(f) for f in mds.features),
                    self._next_rng(),
                    jnp.asarray(self.iteration, jnp.int32),
                    jnp.asarray(self.epoch, jnp.int32),
                )
                self.params_ = {**self.params_, name: new_p}
                self.opt_state_ = {**self.opt_state_, name: new_o}
                self.score_ = loss
                self.iteration += 1
            it.reset()
        return self

    # ----------------------------------------------------------------- tBPTT
    def _init_carries(self, batch: int, dtype=jnp.float32) -> Dict[str, Any]:
        from deeplearning4j_tpu.nn.conf.layers.recurrent import BaseRecurrentLayer

        carries: Dict[str, Any] = {}
        for name in self.layer_names:
            layer = self._layer(name)
            if isinstance(layer, BaseRecurrentLayer):
                carries[name] = layer.init_carry(batch, dtype)
        return carries

    def _make_tbptt_step(self):
        names = self.layer_names
        layers = [self._layer(n) for n in names]
        remat_policy = _resolve_remat_policy(
            getattr(self.conf.global_conf, "remat_policy", None)
        )

        def step(params, opt_state, state, carries, features, labels, fmasks,
                 lmasks, rng, iteration, epoch):
            def loss_fn(p):
                _, _, out_inputs, new_state, new_carries = self._forward(
                    p, state, features, train=True, rng=rng, fmasks=fmasks,
                    carries=carries,
                )
                loss = jnp.asarray(0.0, jnp.float32)
                for i, oname in enumerate(self.conf.network_outputs):
                    layer = self._layer(oname)
                    x, m = out_inputs[oname]
                    if self._compute_dtype is not None:
                        x = x.astype(jnp.float32)
                    lmask = lmasks[i] if (lmasks is not None and i < len(lmasks)) else None
                    if lmask is None:
                        lmask = m
                    p_out = apply_weight_noise(
                        layer, p[oname], rng is not None,
                        jax.random.fold_in(rng, i) if rng is not None else None,
                    )
                    per_ex = layer.compute_score(p_out, x, labels[i], lmask)
                    loss = loss + jnp.mean(per_ex)
                # auxiliary layer losses (MoE load-balancing), as in
                # _loss_and_new_state
                for st in new_state.values():
                    if isinstance(st, dict) and "aux_loss" in st:
                        loss = loss + st["aux_loss"]
                return loss, (new_state, new_carries)

            if remat_policy is not None:
                loss_fn = jax.checkpoint(loss_fn, policy=remat_policy)
            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
            t = iteration + 1
            p_list = [params[n] for n in names]
            g_list = [grads[n] for n in names]
            o_list = [opt_state[n] for n in names]
            np_list, no_list = _apply_layer_updates(
                layers, p_list, g_list, o_list, t, iteration, epoch
            )
            # tBPTT truncation is inherent: carries cross chunks only as
            # fresh step INPUTS (each chunk is its own jit call), so no
            # gradient flows across the boundary (reference
            # ComputationGraph.java:1947 semantics)
            score = loss + self._reg_score(params)
            return (dict(zip(names, np_list)), dict(zip(names, no_list)),
                    new_state, new_carries, score)

        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _fit_tbptt_batch(self, mds: MultiDataSet):
        """Chunked truncated-BPTT over the time axis (reference
        ``doTruncatedBPTT`` on ComputationGraph): every 3D feature/label/
        mask is sliced by ``tbptt_fwd_length``; recurrent carries thread
        across chunks with stop_gradient at boundaries."""
        if self._active_fault_policy() is not None:
            # the one fit path without the guard (ARCHITECTURE.md known
            # gap) — tell the user their configured protection is
            # inactive here instead of silently applying poisoned updates
            import warnings

            warnings.warn(
                "fault_policy is not applied on the ComputationGraph "
                "tBPTT path: non-finite gradient chunks are NOT skipped "
                "and loss scaling is off (use MultiLayerNetwork tBPTT or "
                "standard backprop for guarded training)",
                stacklevel=3,
            )
        step = self._get_jit("tbptt", self._make_tbptt_step)
        T = mds.features[0].shape[1]
        L = self.conf.tbptt_fwd_length
        for lab in mds.labels:
            if lab is not None and lab.ndim != 3:
                raise ValueError(
                    "tBPTT requires per-timestep labels (batch, time, nOut); "
                    f"got shape {lab.shape}"
                )
        carries = self._init_carries(mds.features[0].shape[0])

        def sl(a, lo, hi, is_mask=False):
            """Slice ONLY genuine time-series arrays: 3D (b, T, c) data or
            2D (b, T) masks. Static 2D feature inputs pass through whole
            even if their width coincides with T."""
            if a is None:
                return None
            a = np.asarray(a)
            seq = (a.ndim == 3 or (is_mask and a.ndim == 2)) and a.shape[1] == T
            return jnp.asarray(a[:, lo:hi]) if seq else jnp.asarray(a)

        for lo in range(0, T, L):
            hi = min(lo + L, T)
            feats = tuple(sl(f, lo, hi) for f in mds.features)
            labels = tuple(sl(l, lo, hi) for l in mds.labels)
            fmasks = tuple(sl(m, lo, hi, is_mask=True) for m in mds.features_masks)
            lmasks = tuple(sl(m, lo, hi, is_mask=True) for m in mds.labels_masks)
            (self.params_, self.opt_state_, self.state_, carries,
             self.score_) = step(
                self.params_, self.opt_state_, self.state_, carries,
                feats, labels, fmasks, lmasks, self._next_rng(),
                jnp.asarray(self.iteration, jnp.int32),
                jnp.asarray(self.epoch, jnp.int32),
            )
        self.iteration += 1
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration, self.epoch)

    # -------------------------------------------------------------- rnn state
    def rnn_clear_previous_state(self):
        self._rnn_carries = None

    def rnn_time_step(self, *inputs) -> List[np.ndarray]:
        """Stateful streaming inference (reference
        ``ComputationGraph.rnnTimeStep``): hidden state persists across
        calls; 2D inputs are treated as a single timestep."""
        feats = []
        squeeze = False
        for x in inputs:
            x = jnp.asarray(x)
            if x.ndim == 2:
                x = x[:, None, :]
                squeeze = True
            feats.append(x)
        if getattr(self, "_rnn_carries", None) is None:
            self._rnn_carries = self._init_carries(feats[0].shape[0],
                                                   feats[0].dtype)
        out_names = list(self.conf.network_outputs)

        def run(params, state, inputs, carries):
            acts, _, _, _, new_carries = self._forward(
                params, state, inputs, train=False, rng=None, carries=carries
            )
            return tuple(acts[n] for n in out_names), new_carries

        fn = self._get_jit("rnn_step", lambda: jax.jit(run))
        ys, self._rnn_carries = fn(self.params_, self.state_, tuple(feats),
                                   self._rnn_carries)
        out = []
        for y in ys:
            y = np.asarray(y)
            out.append(y[:, -1, :] if (squeeze and y.ndim == 3) else y)
        return out

    # -------------------------------------------------------------- inference
    def _make_output_fn(self):
        out_names = list(self.conf.network_outputs)

        def run(params, state, inputs, fmasks):
            acts, _, _, _ = self._forward(
                params, state, inputs, train=False, rng=None, fmasks=fmasks
            )
            return tuple(acts[n] for n in out_names)

        return jax.jit(run)

    def output(self, *inputs, masks: Optional[Sequence] = None) -> List[np.ndarray]:
        """Multi-output inference (reference ``output:1759``). Returns a list
        of arrays, one per network output."""
        fn = self._get_jit("output", self._make_output_fn)
        feats = tuple(jnp.asarray(x) for x in inputs)
        fmasks = (
            tuple(None if m is None else jnp.asarray(m) for m in masks)
            if masks is not None else tuple(None for _ in feats)
        )
        ys = fn(self.params_, self.state_, feats, fmasks)
        return [np.asarray(y) for y in ys]

    def output_single(self, *inputs, masks=None) -> np.ndarray:
        ys = self.output(*inputs, masks=masks)
        if len(ys) != 1:
            raise ValueError(f"Graph has {len(ys)} outputs; use output()")
        return ys[0]

    def feed_forward(self, *inputs, train: bool = False) -> Dict[str, np.ndarray]:
        """All vertex activations (reference ``feedForward:1409``)."""
        acts, _, _, _ = self._forward(
            self.params_, self.state_,
            tuple(jnp.asarray(x) for x in inputs),
            train=train, rng=self._next_rng() if train else None,
        )
        return {k: np.asarray(v) for k, v in acts.items()}

    # ------------------------------------------------------------------ score
    def score(self, ds: Optional[Union[DataSet, MultiDataSet]] = None) -> float:
        if ds is None:
            if self.score_ is None:
                raise ValueError("No score available; fit() first or pass a DataSet")
            return float(self.score_)
        mds = _as_multi(ds)

        def run(params, state, feats, labels, fmasks, lmasks):
            loss, _ = self._loss_and_new_state(
                params, state, feats, labels, fmasks, lmasks, None, train=False
            )
            return loss + self._reg_score(params)

        fn = self._get_jit("score", lambda: jax.jit(run))
        return float(fn(
            self.params_, self.state_,
            tuple(jnp.asarray(f) for f in mds.features),
            tuple(jnp.asarray(l) for l in mds.labels),
            tuple(None if m is None else jnp.asarray(m) for m in mds.features_masks),
            tuple(None if m is None else jnp.asarray(m) for m in mds.labels_masks),
        ))

    def compute_gradient_and_score(self, ds: Union[DataSet, MultiDataSet]):
        """(reference ``computeGradientAndScore():1321``)."""
        mds = _as_multi(ds)

        def run(params, state, feats, labels, fmasks, lmasks, rng):
            def loss_fn(p):
                loss, _ = self._loss_and_new_state(
                    p, state, feats, labels, fmasks, lmasks, rng, train=True
                )
                return loss

            loss, grads = jax.value_and_grad(loss_fn)(params)
            return grads, loss + self._reg_score(params)

        fn = self._get_jit("grad_score", lambda: jax.jit(run))
        grads, score = fn(
            self.params_, self.state_,
            tuple(jnp.asarray(f) for f in mds.features),
            tuple(jnp.asarray(l) for l in mds.labels),
            tuple(None if m is None else jnp.asarray(m) for m in mds.features_masks),
            tuple(None if m is None else jnp.asarray(m) for m in mds.labels_masks),
            self._next_rng(),
        )
        return grads, float(score)

    # ------------------------------------------------------------- evaluation
    def _evaluate_with(self, it, ev):
        """Shared drive loop for the evaluate-family helpers."""
        if isinstance(it, DataSet):
            it = ListDataSetIterator(it, 256)
        for ds in it:
            out = self.output_single(ds.features, masks=[ds.features_mask])
            ev.eval(ds.labels, out, mask=ds.labels_mask)
        it.reset()
        return ev

    def evaluate_roc(self, it, threshold_steps: int = 0):
        """Binary ROC (reference ``evaluateROC``)."""
        from deeplearning4j_tpu.evaluation import ROC

        return self._evaluate_with(it, ROC(threshold_steps))

    def evaluate_roc_multi_class(self, it, threshold_steps: int = 0):
        """One-vs-all ROC per class (reference ``evaluateROCMultiClass``)."""
        from deeplearning4j_tpu.evaluation import ROCMultiClass

        return self._evaluate_with(it, ROCMultiClass(threshold_steps))

    def evaluate(self, it: Union[DataSetIterator, DataSet], top_n: int = 1):
        """(reference ``evaluate`` incl. the topN overload)"""
        from deeplearning4j_tpu.evaluation import Evaluation

        return self._evaluate_with(it, Evaluation(top_n=top_n))

    def evaluate_regression(self, it: Union[DataSetIterator, DataSet]):
        """(reference ``evaluateRegression``)"""
        from deeplearning4j_tpu.evaluation import RegressionEvaluation

        return self._evaluate_with(it, RegressionEvaluation())

    # ------------------------------------------------------- params utilities
    def num_params(self) -> int:
        assert self.params_ is not None
        return int(sum(int(np.prod(a.shape))
                       for n in self.layer_names for a in self.params_[n].values()))

    def summary(self) -> str:
        """Vertex table — name, kind, inputs, #params (reference
        ``ComputationGraph.summary()``)."""
        rows = [("vertex", "kind", "inputs", "params")]
        total = 0
        for name in self.topo:
            v = self.conf.vertices[name]
            kind = (type(v.layer).__name__ if isinstance(v, LayerVertex)
                    else type(v).__name__)
            srcs = ", ".join(self.conf.vertex_inputs.get(name, ()))
            n = 0
            if (self.params_ is not None and name in self.params_
                    and isinstance(v, LayerVertex)):
                n = int(sum(int(np.prod(a.shape))
                            for a in self.params_[name].values()))
            total += n
            rows.append((name, kind, srcs, f"{n:,}"))
        for name in self.conf.network_inputs:
            rows.insert(1, (name, "NetworkInput", "", "0"))
        widths = [max(len(r[c]) for r in rows) for c in range(4)]
        lines = ["  ".join(r[c].ljust(widths[c]) for c in range(4))
                 for r in rows]
        lines.insert(1, "-" * (sum(widths) + 6))
        lines.append(f"Total parameters: {total:,}")
        return "\n".join(lines)

    def params_flat(self) -> np.ndarray:
        """Flattened parameter vector (order: topo layer order, param name
        sorted — deterministic for checkpointing)."""
        assert self.params_ is not None
        chunks = []
        for n in self.layer_names:
            p = self.params_[n]
            for pn in sorted(p):
                chunks.append(np.asarray(p[pn], np.float32).reshape(-1))
        if not chunks:
            return np.zeros((0,), np.float32)
        return np.concatenate(chunks)

    def set_params_flat(self, vec: np.ndarray) -> None:
        assert self.params_ is not None
        vec = np.asarray(vec, np.float32)
        off = 0
        new_params = dict(self.params_)
        for n in self.layer_names:
            p = self.params_[n]
            np_i = {}
            for pn in sorted(p):
                cnt = int(np.prod(p[pn].shape))
                np_i[pn] = jnp.asarray(vec[off:off + cnt].reshape(p[pn].shape), p[pn].dtype)
                off += cnt
            new_params[n] = np_i
        if off != vec.size:
            raise ValueError(f"Param vector length {vec.size} != model size {off}")
        self.params_ = new_params

    def opt_state_flat(self) -> np.ndarray:
        assert self.opt_state_ is not None
        chunks = []
        for n in self.layer_names:
            o = self.opt_state_[n]
            for pn in sorted(o):
                for slot in sorted(o[pn]):
                    chunks.append(np.asarray(o[pn][slot], np.float32).reshape(-1))
        if not chunks:
            return np.zeros((0,), np.float32)
        return np.concatenate(chunks)

    def set_opt_state_flat(self, vec: np.ndarray) -> None:
        assert self.opt_state_ is not None
        vec = np.asarray(vec, np.float32)
        off = 0
        new_opt = dict(self.opt_state_)
        for n in self.layer_names:
            o = self.opt_state_[n]
            no_i = {}
            for pn in sorted(o):
                slots = {}
                for slot in sorted(o[pn]):
                    arr = o[pn][slot]
                    cnt = int(np.prod(arr.shape))
                    slots[slot] = jnp.asarray(vec[off:off + cnt].reshape(arr.shape), arr.dtype)
                    off += cnt
                no_i[pn] = slots
            new_opt[n] = no_i
        self.opt_state_ = new_opt

    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    def add_listeners(self, *listeners) -> None:
        self.listeners.extend(listeners)

    def set_learning_rate(self, lr: float) -> None:
        """Set the learning rate on every layer vertex's updater
        (reference ``ComputationGraph.setLearningRate``); takes effect on
        the next jitted step. The conf is network-private (see __init__),
        so sibling networks are unaffected."""
        from deeplearning4j_tpu.schedules import as_schedule

        for name in self.layer_names:
            upd = self._layer(name).updater
            if upd is not None and getattr(upd, "has_learning_rate", False):
                upd.learning_rate = as_schedule(float(lr))
        self._jit_cache.clear()

    setLearningRate = set_learning_rate

    def clone(self) -> "ComputationGraph":
        conf = ComputationGraphConfiguration.from_json(self.conf.to_json())
        net = ComputationGraph(conf, copy_conf=False)
        if self.params_ is not None:
            # deep copy, no init(): the source's train step donates its
            # buffers to XLA, so shared arrays would be deleted under it
            net.params_ = jax.tree_util.tree_map(jnp.copy, self.params_)
            net.state_ = jax.tree_util.tree_map(jnp.copy, self.state_)
            net.opt_state_ = jax.tree_util.tree_map(jnp.copy, self.opt_state_)
            net.iteration = self.iteration
            net.epoch = self.epoch
        return net
