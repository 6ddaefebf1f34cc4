"""Hand-written Pallas TPU kernels — the SURVEY §7 "Pallas for the hot
ops" path (the reference's analog is the cuDNN helper layer, §2.4,
absorbed elsewhere by XLA lowering; these kernels exist where XLA's
op-boundary materialization costs real HBM traffic).

The kernel SUBSYSTEM (this package):

- ``flash_attention`` / ``fused_conv`` — the attention/conv fast paths;
- ``fused_lstm`` — the LSTM cell (training scan + engine decode);
- ``fused_update`` — the single-pass ZeRO-1 Adam update;
- ``int8_matmul`` — int8 weight-quantized serving matmul;
- ``latent_decode`` — a decode step's attention over a latent cache, by
  the slots' live lengths;
- ``ssm_decode`` — a decode step's state-space recurrence, readout and
  in-place update from one read of the live slots' state;
- ``kv_column_write`` — a decode step's new key/value column a live slot
  into a time-minor cache slab, block by block in place;
- ``decode_attention`` — a decode step's attention over the K and V
  slabs where they lie, the live column tiles of the live slots only;
- ``grouped_experts`` — an expert layer's grouped SwiGLU products at a
  decode step's row counts: each hit expert's matrices read once, in wide
  tiles, against a short window of its rows;
- ``sparse_latent_decode`` — a decode step's latent attention over an
  indexer's selection: the live slots' rows streamed once, where they lie,
  under the selection's bias;
- ``registry`` — the shared probe-once/fallback/observability contract
  every kernel resolves through (``KernelRegistry``).
"""

from deeplearning4j_tpu.nn.ops.flash_attention import flash_attention
from deeplearning4j_tpu.nn.ops.registry import (
    KernelRegistry,
    default_kernel_registry,
)

__all__ = ["flash_attention", "KernelRegistry", "default_kernel_registry"]
