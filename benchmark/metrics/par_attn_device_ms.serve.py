"""Of ``par_mixer_device_ms.serve``, the device milliseconds a decode step
spends under ``attn_full``: the attention branch of every parallel block,
the part that grows with the positions behind the slots. The rest of that
metric is the state-space branch and the join."""

from lib import parallel_read


def read(run):
    return parallel_read.scope_ms(("attn_full",), run["work"].get("decode_program"))
