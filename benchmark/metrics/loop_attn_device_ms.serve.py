"""Device milliseconds a decode step spends under ``attn_full``, every
(pass, layer) entry of it: the norms, the four projections, the rotation and
the attention over the slot's keys and values. The part of the step that
grows with the cache (a full layer's einsum reads its slab whole) beside the
part that the weights set."""

from lib import looped_read


def read(run):
    scopes = looped_read.scope_seconds(run["work"].get("decode_program"))
    if scopes is None:
        return None
    by_scope, runs = scopes
    return 1e3 * by_scope.get("attn_full", 0.0) / runs
