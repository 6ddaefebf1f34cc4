"""Expert parallelism (EP) — shard MoE expert FFNs over the mesh
"expert" axis. A NEW capability of this stack (SURVEY.md §2.5: EP is
ABSENT in the reference).

TPU-native shape: no parameter server, no explicit routing collective —
the expert-leading params (``W1/b1/W2/b2`` of
``nn/conf/layers/moe.py``) get a ``P("expert", ...)`` sharding and the
batch gets ``P("data", ...)``; GSPMD then lowers the dense-dispatch
einsums (``sec,sd->ecd`` / ``sec,ecd->sd``) to the token all-to-all
between data and expert shards. The network's own jitted train step is
reused unchanged — placement alone turns it into an EP program.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn.conf.layers.moe import (
    MixtureOfExpertsLayer,
    MoETransformerBlock,
    moe_dropless_ffn,
    shared_swiglu,
    sigmoid_topk_route,
)
from deeplearning4j_tpu.parallel.mesh import TrainingMesh

_EXPERT_PARAMS = ("W1", "b1", "W2", "b2")


def expert_parallel_dropless_ffn(x, router_in, params, top_k: int,
                                 expert_axis: str, token_mask=None,
                                 route=sigmoid_topk_route,
                                 shared: bool = False):
    """The dropless expert layer (``moe_dropless_ffn``) under MANUAL
    expert parallelism, for use inside a ``shard_map`` region: ``Eg`` /
    ``Eu`` / ``Ed`` arrive with their expert dimension sliced over
    ``expert_axis`` and the router (``Wr``, ``br``) replicated. Every
    shard routes its tokens over ALL experts, computes the share of the
    experts it holds (``experts_held`` = its slice), and the shares,
    which are disjoint, are summed over the axis. The shared expert
    (``shared``; its weights replicated) is computed by every shard alike
    and counted once: added after the sum. Returns (y, pairs computed,
    held experts hit), each over the whole axis."""
    count = params["Eg"].shape[0]
    held = (jax.lax.axis_index(expert_axis) * count, count)
    share = moe_dropless_ffn(x, router_in, params, top_k, held, token_mask,
                             route=route)
    y, pairs, hit = (jax.lax.psum(part, expert_axis) for part in share)
    if shared:
        y = y + shared_swiglu(x, params)
    return y, pairs, hit


class ExpertParallelWrapper:
    """Place a network with MoE layers onto a ("data", ..., "expert")
    mesh and step it with the model's own jitted train step.

    Works for MultiLayerNetwork (list-of-dict params) and
    ComputationGraph (dict-of-dict params)."""

    def __init__(self, model, mesh: TrainingMesh):
        self.model = model
        self.mesh = mesh
        n_exp = {
            l.n_experts for l in self._layers().values()
            if isinstance(l, (MixtureOfExpertsLayer, MoETransformerBlock))
        }
        if not n_exp:
            raise ValueError("model has no MoE layers to expert-shard")
        ep = mesh.shape.get("expert", 1)
        for e in n_exp:
            if e % ep:
                raise ValueError(
                    f"n_experts={e} not divisible by mesh expert axis {ep}"
                )

    # ------------------------------------------------------------ structure
    def _layers(self) -> Dict[Any, Any]:
        m = self.model
        if hasattr(m, "layer_names"):  # ComputationGraph
            return {n: m._layer(n) for n in m.layer_names}
        return dict(enumerate(m.layers))  # MultiLayerNetwork

    def _spec_for(self, layer, pname: str, leaf) -> P:
        if (isinstance(layer, (MixtureOfExpertsLayer, MoETransformerBlock))
                and pname in _EXPERT_PARAMS):
            return P("expert", *([None] * (leaf.ndim - 1)))
        return P()

    # ------------------------------------------------------------ placement
    def place(self) -> "ExpertParallelWrapper":
        m, mesh = self.model, self.mesh.mesh
        layers = self._layers()

        def put_param_dict(key, pdict):
            layer = layers[key]
            out = {}
            for pname, v in pdict.items():
                spec = self._spec_for(layer, pname, v)
                out[pname] = jax.device_put(v, NamedSharding(mesh, spec))
            return out

        def put_opt_dict(key, odict, pdict):
            layer = layers[key]
            out = {}
            for pname, slot in odict.items():
                spec = (self._spec_for(layer, pname, pdict[pname])
                        if pname in pdict else P())
                out[pname] = jax.tree_util.tree_map(
                    lambda s: jax.device_put(
                        s, NamedSharding(
                            mesh,
                            spec if getattr(s, "shape", None)
                            == pdict[pname].shape else P())),
                    slot,
                )
            return out

        def put_replicated(tree):
            return jax.tree_util.tree_map(
                lambda s: jax.device_put(s, NamedSharding(mesh, P())), tree)

        if hasattr(m, "layer_names"):  # CG: dict keyed by vertex name
            m.params_ = {k: put_param_dict(k, v) for k, v in m.params_.items()}
            m.opt_state_ = {k: put_opt_dict(k, v, m.params_[k])
                            for k, v in m.opt_state_.items()}
            m.state_ = put_replicated(m.state_)
        else:  # MLN: lists indexed by layer
            m.params_ = [put_param_dict(i, p) for i, p in enumerate(m.params_)]
            m.opt_state_ = [put_opt_dict(i, o, m.params_[i])
                            for i, o in enumerate(m.opt_state_)]
            m.state_ = put_replicated(m.state_)
        return self

    # ------------------------------------------------------------- stepping
    def fit_batch(self, x: np.ndarray, y: np.ndarray) -> float:
        """One EP train step; batch sharded over "data"."""
        m = self.model
        bs = self.mesh.batch_sharded()
        x = jax.device_put(jnp.asarray(x), bs)
        y = jax.device_put(jnp.asarray(y), bs)
        if hasattr(m, "layer_names"):  # ComputationGraph
            step = m._get_jit("train", m._make_train_step)
            (m.params_, m.opt_state_, m.state_, m.score_) = step(
                m.params_, m.opt_state_, m.state_, (x,), (y,), (None,), (None,),
                m._next_rng(), jnp.asarray(m.iteration, jnp.int32),
                jnp.asarray(m.epoch, jnp.int32),
            )
        else:  # MultiLayerNetwork
            step = m._get_jit("train", m._make_train_step)
            (m.params_, m.opt_state_, m.state_, m.score_) = step(
                m.params_, m.opt_state_, m.state_, x, y, None, None,
                m._next_rng(), jnp.asarray(m.iteration, jnp.int32),
                jnp.asarray(m.epoch, jnp.int32),
            )
        m.iteration += 1
        return float(m.score_)
