"""FusedResNetBottleneck: one ResNet bottleneck block (1x1 reduce → 3x3
→ 1x1 expand, + identity/projection shortcut) as a SINGLE layer driving
the Pallas fused conv+BN+ReLU kernels (``nn/ops/fused_conv.py``; VERDICT
r3 item 1 — the TPU-native counterpart of the reference's cuDNN conv
fast path, ``deeplearning4j-cuda/.../CudnnConvolutionHelper.java:1``,
which likewise swaps a faster implementation in behind the layer SPI).

Dataflow (train): each conv emits its RAW output plus per-channel
(sum, sum²) statistics in one pass; the next conv folds the upstream
normalize+ReLU into its input read. Per-channel BN coefficient math
(gamma/beta/mean/var → scale/shift) happens here in plain jnp on (C,)
vectors, so jax autodiff chains the cross-layer statistics gradients
through the kernels' custom VJPs automatically. Only the block output
(after the residual add) is materialized normalized — the interior
normalized activations never exist in HBM.

Falls back to an XLA composition with IDENTICAL parameter/state layout
when the Pallas ops don't pass the compile-probe or when the compute
dtype isn't bf16 (fp64 gradient checks).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf import serde
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.base import FeedForwardLayer


@serde.register
class FusedResNetBottleneck(FeedForwardLayer):
    """width → the bottleneck channel count (output channels = 4*width);
    ``stride=2`` subsamples in the reduce conv and the projection (the
    torchvision/reference ResNet-50 geometry); ``project=True`` adds the
    1x1 projection shortcut (first block of each stage)."""

    #: BN affine params stay fp32 under mixed precision (matching the
    #: standalone BatchNormalization layer's exclusion from compute casts)
    keep_fp32_params = ("gamma_a", "beta_a", "gamma_b", "beta_b",
                        "gamma_c", "beta_c", "gamma_p", "beta_p")

    def __init__(self, width: int, stride: int = 1, project: bool = False,
                 decay: float = 0.9, eps: float = 1e-5,
                 use_pallas: Optional[bool] = None, **kwargs):
        kwargs.setdefault("n_out", 4 * int(width))
        super().__init__(**kwargs)
        self.width = int(width)
        self.stride = int(stride)
        self.project = bool(project)
        self.decay = float(decay)
        self.eps = float(eps)
        self.use_pallas = use_pallas

    # ----------------------------------------------------------------- conf
    def initialize(self, input_type: InputType) -> None:
        if input_type.kind != "convolutional":
            raise ValueError("FusedResNetBottleneck needs convolutional input")
        if self.n_in is None:
            self.n_in = input_type.channels
        if not self.project and self.n_in != 4 * self.width:
            raise ValueError(
                f"identity shortcut needs n_in == 4*width "
                f"({self.n_in} != {4 * self.width}); set project=True")
        if self.stride == 2 and not self.project:
            raise ValueError("stride-2 blocks need a projection shortcut")

    def get_output_type(self, input_type: InputType) -> InputType:
        h = math.ceil(input_type.height / self.stride)
        w = math.ceil(input_type.width / self.stride)
        return InputType.convolutional(h, w, 4 * self.width)

    # --------------------------------------------------------------- params
    def init_params(self, rng, input_type, dtype=jnp.float32):
        assert self.n_in is not None
        wd, cin, cout = self.width, self.n_in, 4 * self.width
        keys = jax.random.split(rng, 4)

        def conv_w(k, shape):
            fan_in = int(np.prod(shape[:-1]))
            fan_out = int(np.prod(shape[:-2])) * shape[-1] if len(shape) > 2 \
                else shape[-1]
            return self._draw_weight(k, shape, fan_in, fan_out, dtype)

        p = {
            "W_a": conv_w(keys[0], (cin, wd)),
            "W_b": conv_w(keys[1], (3, 3, wd, wd)),
            "W_c": conv_w(keys[2], (wd, cout)),
        }
        for tag, c in (("a", wd), ("b", wd), ("c", cout)):
            p[f"gamma_{tag}"] = jnp.ones((c,), jnp.float32)
            p[f"beta_{tag}"] = jnp.zeros((c,), jnp.float32)
        if self.project:
            p["W_p"] = conv_w(keys[3], (cin, cout))
            p["gamma_p"] = jnp.ones((cout,), jnp.float32)
            p["beta_p"] = jnp.zeros((cout,), jnp.float32)
        return p

    def init_layer_state(self, input_type, dtype=jnp.float32):
        wd, cout = self.width, 4 * self.width
        s = {}
        for tag, c in (("a", wd), ("b", wd), ("c", cout)):
            s[f"mean_{tag}"] = jnp.zeros((c,), jnp.float32)
            s[f"var_{tag}"] = jnp.ones((c,), jnp.float32)
        if self.project:
            s["mean_p"] = jnp.zeros((cout,), jnp.float32)
            s["var_p"] = jnp.ones((cout,), jnp.float32)
        return s

    # ---------------------------------------------------------------- apply
    def _pallas_enabled(self, x) -> bool:
        """Whether this block drives the Pallas kernels (both convs) or
        the XLA composition (both convs) — the choice is deliberately
        block-global.

        Hardware verdict (2026-07-31, one v5e, batch 128, fwd+bwd
        wall-clock): in ISOLATION the 3x3 kernel beats its XLA
        composition mid-network (0.83x at (28,28,128), 0.71x at
        (14,14,256)) and the pointwise kernel is parity-at-best (4.2x
        worse at stage 1, where 64→128 channel padding idles half the
        MXU K-dim). But mixing per-shape does NOT compose: a ResNet-50
        step with only the winning c3 shapes on Pallas measured 885
        img/s vs 1228 all-Pallas vs 2615 all-XLA — every Pallas custom
        call is a fusion/layout boundary that costs XLA more than the
        kernel saves. So: both kernels or neither, and the XLA path
        stays the default/headline (``ResNet50(fused_pallas=True)``
        opts in). DL4J_TPU_FUSED: "0" disables. The compile-probe
        verdict is always consulted — a kernel that fails its value
        check never runs."""
        import os

        env = os.environ.get("DL4J_TPU_FUSED")
        if env == "0":
            return False
        if env is None and self.use_pallas is False:
            return False
        if x.dtype != jnp.bfloat16:
            return False
        from deeplearning4j_tpu.nn.ops.fused_conv import fused_conv_available

        return fused_conv_available(x.dtype)

    def _bn_fold(self, stats, count, gamma, beta, r_mean, r_var, train):
        """stats (2, C) from the conv epilogue → fold coefficients
        (scale, shift) f32 for the downstream consumer + new running
        stats. Math mirrors BatchNormalization.apply (decay EMA,
        eps inside rsqrt)."""
        if train:
            mean = stats[0] / count
            var = jnp.maximum(stats[1] / count - mean * mean, 0.0)
            new_running = (
                jax.lax.stop_gradient(
                    self.decay * r_mean + (1 - self.decay) * mean),
                jax.lax.stop_gradient(
                    self.decay * r_var + (1 - self.decay) * var),
            )
        else:
            mean, var = r_mean, r_var
            new_running = (r_mean, r_var)
        inv = jax.lax.rsqrt(var + self.eps)
        scale = gamma * inv
        shift = beta - mean * inv * gamma
        return scale, shift, new_running

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        assert state is not None and "mean_a" in state
        from deeplearning4j_tpu.nn.ops import fused_conv as fc

        use_pallas = self._pallas_enabled(x)

        def pw(xm, s, t, w, relu_in):
            if use_pallas:
                return fc.pw_conv(xm, s, t, w, relu_in, False)
            return fc.pw_conv_reference(xm, s, t, w, relu_in)

        def c3(x4, s, t, w, relu_in):
            if use_pallas:
                return fc.conv3x3(x4, s, t, w, relu_in, False)
            return fc.conv3x3_reference(x4, s, t, w, relu_in)

        n, h, w_sp, cin = x.shape
        wd, cout = self.width, 4 * self.width
        x_in = x[:, ::2, ::2, :] if self.stride == 2 else x
        hs, ws = x_in.shape[1], x_in.shape[2]
        m = n * hs * ws
        ones = jnp.ones((cin,), jnp.float32)
        zeros = jnp.zeros((cin,), jnp.float32)

        # conv a: block input is already normalized — no fold
        za, st_a = pw(x_in.reshape(m, cin), ones, zeros, params["W_a"], False)
        s_a, t_a, run_a = self._bn_fold(
            st_a, m, params["gamma_a"], params["beta_a"],
            state["mean_a"], state["var_a"], train)
        # conv b: fold a's normalize+relu into the read
        zb, st_b = c3(za.reshape(n, hs, ws, wd), s_a, t_a, params["W_b"],
                      True)
        s_b, t_b, run_b = self._bn_fold(
            st_b, m, params["gamma_b"], params["beta_b"],
            state["mean_b"], state["var_b"], train)
        # conv c: fold b's normalize+relu
        zc, st_c = pw(zb.reshape(m, wd), s_b, t_b, params["W_c"], True)
        s_c, t_c, run_c = self._bn_fold(
            st_c, m, params["gamma_c"], params["beta_c"],
            state["mean_c"], state["var_c"], train)

        dt = x.dtype
        nc = zc.reshape(n, hs, ws, cout).astype(dt) * s_c.astype(dt) \
            + t_c.astype(dt)
        new_state = {
            "mean_a": run_a[0], "var_a": run_a[1],
            "mean_b": run_b[0], "var_b": run_b[1],
            "mean_c": run_c[0], "var_c": run_c[1],
        }
        if self.project:
            zp, st_p = pw(x_in.reshape(m, cin), ones, zeros, params["W_p"],
                          False)
            s_p, t_p, run_p = self._bn_fold(
                st_p, m, params["gamma_p"], params["beta_p"],
                state["mean_p"], state["var_p"], train)
            shortcut = zp.reshape(n, hs, ws, cout).astype(dt) \
                * s_p.astype(dt) + t_p.astype(dt)
            new_state["mean_p"] = run_p[0]
            new_state["var_p"] = run_p[1]
        else:
            shortcut = x
        # the only materialized-normalized tensor of the block: the
        # residual output (XLA fuses normalize+add+relu into one pass)
        return jnp.maximum(nc + shortcut, 0), new_state
