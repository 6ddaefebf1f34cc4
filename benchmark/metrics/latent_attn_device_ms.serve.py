"""Device milliseconds a decode step spends in latent attention, all
layers: ``attn_latent_proj`` (norms, the low-rank projections, rotation,
absorption into the latent space, output projection: bound by weights) and
``attn_latent_core`` (scores over the latent cache, softmax, the weighted
sum of latents: bound by the cache's bytes and by compute at once). Self
time inside the decode program's executions of the traced window over
their number."""

from lib import latent_read


def read(run):
    return latent_read.scope_ms(latent_read.LATENT_SCOPES, run["work"].get("decode_program"))
