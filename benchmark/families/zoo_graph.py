"""Family ``zoo_graph``: zoo models built on the ``ComputationGraph``
runtime (``nn/graph.py``), trained through the public ``fit`` over a
``DataSet`` as a DL4J user trains. The configuration names the zoo class
(``zoo_model``), its reference (``reference``, a module under
``reference/`` with ``make_weights``, ``train_steps`` and
``train_flops_per_item``) and, under ``program_names``, the layers that the
program names otherwise than the reference (whose ``a.b.conv`` is the
program's ``a_b_conv``).

The weights come from the reference's ``make_weights`` (the benchmark's own
generator, from ``--seed``) and are handed to the program under its layer
names; the values that the program's own ``init`` draws are never used.
"""

import gc
import importlib

import jax
import jax.numpy as jnp
import numpy as np


def _reference(config):
    return importlib.import_module("reference." + config["reference"])


def program_tree(config, weights, like):
    """The program's parameter tree (every layer name it has, empty dicts
    for layers without parameters) filled from the reference's weights."""
    renamed = config.get("program_names", {})
    out = {name: {} for name in like}
    for ref_name, layer in weights.items():
        name = renamed.get(ref_name, ref_name.replace(".", "_"))
        if set(like[name]) != set(layer):
            raise ValueError(f"layer {name}: program has {sorted(like[name])}, reference {sorted(layer)}")
        out[name] = dict(layer)
    missing = [n for n in like if like[n] and not out[n]]
    if missing:
        raise ValueError(f"the reference has no weights for {missing}")
    return out


def _reference_leaves(config, tree):
    """{"a.b.conv/W": array} from the program's tree"""
    back = {v: k for k, v in config.get("program_names", {}).items()}
    return {f"{back.get(name, name.replace('_', '.'))}/{k}": a
            for name, layer in tree.items() for k, a in layer.items()}


def _norms(leaves):
    names = list(leaves)
    values = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))) for a in xs])(
        [leaves[n] for n in names])
    return {n: float(v) for n, v in zip(names, values)}


def item_shape(config, traffic):
    """Items (images) in one training step."""
    return traffic["batch"]


def batch_source(config, traffic, rng):
    """``next_batch()``: a few distinct seeded host batches, cycled (drawing
    19 M normals a step would cost more host time than the step). Images
    are float32 standard normal, labels one-hot."""
    n, hw, classes = traffic["batch"], config["image_size"], config["num_classes"]
    pool = [(rng.standard_normal((n, hw, hw, 3), dtype=np.float32),
             np.eye(classes, dtype=np.float32)[rng.integers(0, classes, n)])
            for _ in range(traffic["distinct_batches"])]
    state = {"i": 0}

    def next_batch():
        batch = pool[state["i"] % len(pool)]
        state["i"] += 1
        return batch

    return next_batch


class Trainer:
    """One network with its compiled step and state, from set-up through
    the window: ``step`` is the public ``fit`` on one ``DataSet``."""

    def __init__(self, config, traffic, seed):
        from deeplearning4j_tpu import models
        from deeplearning4j_tpu.updaters import Nesterovs

        self.config, self.seed = config, seed
        self.ref = _reference(config)
        opt = config["optimizer"]
        zoo = getattr(models, config["zoo_model"])
        self.net = zoo(num_classes=config["num_classes"], height=config["image_size"],
                       width=config["image_size"], compute_dtype=config["compute_dtype"],
                       updater=Nesterovs(opt["learning_rate"], opt["momentum"])).init()
        self.net.params_ = program_tree(config, self.ref.make_weights(config, seed), self.net.params_)
        self.items_per_step = item_shape(config, traffic)
        self.batch = traffic["batch"]

    def step(self, batch):
        from deeplearning4j_tpu.data.dataset import DataSet

        self.net.fit(DataSet(*batch), epochs=1, batch_size=self.batch)
        return float(self.net.score_)

    def steps_taken(self):
        return int(self.net.iteration)

    def first_gradient_norms(self):
        """Leaf norms of the gradient the updater got in step 1 (with the
        L2 term), from the momentum after that step: v1 = -lr g."""
        v = {name: {k: s["v"] for k, s in layer.items()}
             for name, layer in self.net.opt_state_.items()}
        lr = self.config["optimizer"]["learning_rate"]
        return {k: n / lr for k, n in _norms(_reference_leaves(self.config, v)).items()}

    def update_norms(self):
        """Leaf norms of (parameters now - parameters from the seed)."""
        start = _reference_leaves(self.config, program_tree(
            self.config, self.ref.make_weights(self.config, self.seed), self.net.params_))
        now = _reference_leaves(self.config, self.net.params_)
        return _norms({k: now[k] - start[k] for k in now})

    def retraces(self):
        return 0

    def close(self):
        self.net.params_ = self.net.opt_state_ = self.net.state_ = None
        self.net = None
        gc.collect()


def reference_train(config, seed, batches, mode="float32"):
    opt = dict(config["optimizer"], l2=config["l2"])
    return _reference(config).train_steps(config, seed, batches, opt, mode)


def work_model(config, traffic):
    """What the roofline readers divide by: the reference counts the
    operations its own network requires."""
    return {"flops_per_item": _reference(config).train_flops_per_item(config)}
