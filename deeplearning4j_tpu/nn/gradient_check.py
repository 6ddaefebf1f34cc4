"""Numerical gradient checking — the test-suite backbone.

Reference: ``gradientcheck/GradientCheckUtil.java:109`` (MultiLayerNetwork),
``:331`` (ComputationGraph) — perturb every parameter ±ε in fp64, compare
relative error against the analytic gradient. The reference checks in
double precision; jax's CPU backend runs fp32 by default, so the checker
promotes the whole computation to float64 via ``jax.enable_x64``
(SURVEY.md §7 hard-part 2: fp64-on-CPU reference for the checker). Tests
call this on tiny nets where the O(P) forward passes are cheap.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet

DEFAULT_EPS = 1e-6
DEFAULT_MAX_REL_ERROR = 1e-3
DEFAULT_MIN_ABS_ERROR = 1e-8


def _to64(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(np.asarray(a), jnp.float64), tree
    )


def _opt64(a):
    return None if a is None else jnp.asarray(np.asarray(a), jnp.float64)


def _central_difference_check(
    loss_fn,
    params64,
    analytic,
    keys,
    eps: float,
    max_rel_error: float,
    min_abs_error: float,
    print_results: bool,
    copy_with,
) -> bool:
    """Shared ±ε loop. ``keys`` iterates container keys (int layer index or
    vertex name); ``copy_with(params, key, name, arr)`` returns a fresh
    params pytree with one array replaced."""
    loss_fn_j = jax.jit(loss_fn)
    total, failed = 0, 0
    max_err_seen = 0.0
    for key in keys:
        for name, arr in params64[key].items():
            flat = np.array(arr, np.float64).reshape(-1)  # writable copy
            g_flat = np.asarray(analytic[key][name], np.float64).reshape(-1)
            for j in range(flat.size):
                orig = flat[j]
                flat[j] = orig + eps
                s_plus = float(loss_fn_j(copy_with(params64, key, name, flat.reshape(arr.shape))))
                flat[j] = orig - eps
                s_minus = float(loss_fn_j(copy_with(params64, key, name, flat.reshape(arr.shape))))
                flat[j] = orig
                numeric = (s_plus - s_minus) / (2 * eps)
                analytic_g = g_flat[j]
                denom = abs(numeric) + abs(analytic_g)
                rel = abs(numeric - analytic_g) / denom if denom > 0 else 0.0
                total += 1
                if rel > max_rel_error and abs(numeric - analytic_g) > min_abs_error:
                    failed += 1
                    if print_results:
                        print(
                            f"FAIL {key} param {name}[{j}]: "
                            f"analytic={analytic_g:.8g} numeric={numeric:.8g} rel={rel:.4g}"
                        )
                max_err_seen = max(max_err_seen, rel if denom > 0 else 0.0)
    if print_results:
        print(f"Gradient check: {total - failed}/{total} passed; max rel err {max_err_seen:.3g}")
    return failed == 0


def _list_copy_with(params, i, name, new_arr):
    out = [dict(p) for p in params]
    out[i][name] = jnp.asarray(new_arr, jnp.float64)
    return out


def _dict_copy_with(params, key, name, new_arr):
    out = {k: dict(v) for k, v in params.items()}
    out[key][name] = jnp.asarray(new_arr, jnp.float64)
    return out


def check_gradients(
    net,
    ds: DataSet,
    eps: float = DEFAULT_EPS,
    max_rel_error: float = DEFAULT_MAX_REL_ERROR,
    min_abs_error: float = DEFAULT_MIN_ABS_ERROR,
    print_results: bool = False,
    rng_seed: int = 12345,
) -> bool:
    """Analytic vs numerical gradients for a MultiLayerNetwork.

    Deterministic rng is reused for every evaluation so dropout/noise layers
    see identical masks (the reference requires deterministic=true layers).
    Returns True if all parameters pass.
    """
    with jax.enable_x64(True):
        params64 = _to64(net.params_)
        state64 = _to64(net.state_)
        f = _opt64(ds.features)
        l = _opt64(ds.labels)
        fm = _opt64(ds.features_mask)
        lm = _opt64(ds.labels_mask)
        rng = jax.random.PRNGKey(rng_seed)

        def loss_fn(p):
            loss, _ = net._loss_and_new_state(p, state64, f, l, fm, lm, rng, train=True)
            return loss + net._reg_score(p)

        analytic = jax.grad(loss_fn)(params64)
        return _central_difference_check(
            loss_fn, params64, analytic, range(len(params64)),
            eps, max_rel_error, min_abs_error, print_results, _list_copy_with,
        )


def check_gradients_graph(
    net,
    mds,
    eps: float = DEFAULT_EPS,
    max_rel_error: float = DEFAULT_MAX_REL_ERROR,
    min_abs_error: float = DEFAULT_MIN_ABS_ERROR,
    print_results: bool = False,
    rng_seed: int = 12345,
) -> bool:
    """ComputationGraph analog (reference ``GradientCheckUtil.java:331``).

    ``mds`` is a MultiDataSet (or DataSet, adapted)."""
    from deeplearning4j_tpu.nn.graph import _as_multi

    mds = _as_multi(mds)
    with jax.enable_x64(True):
        params64 = _to64(net.params_)
        state64 = _to64(net.state_)
        feats = tuple(_opt64(f) for f in mds.features)
        labels = tuple(_opt64(l) for l in mds.labels)
        fmasks = tuple(_opt64(m) for m in mds.features_masks)
        lmasks = tuple(_opt64(m) for m in mds.labels_masks)
        rng = jax.random.PRNGKey(rng_seed)

        def loss_fn(p):
            loss, _ = net._loss_and_new_state(
                p, state64, feats, labels, fmasks, lmasks, rng, train=True
            )
            return loss + net._reg_score(p)

        analytic = jax.grad(loss_fn)(params64)
        return _central_difference_check(
            loss_fn, params64, analytic, list(params64),
            eps, max_rel_error, min_abs_error, print_results, _dict_copy_with,
        )
