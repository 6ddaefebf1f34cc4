"""Device milliseconds ONE pass over the layer stack costs in decode: the
decode program's self time under ``attn_full`` + ``mlp`` + ``pass_close``
per execution in the traced window, over the passes a step ran
(``stack_passes`` / ``decode_steps`` of the traced span). A pass reads every
layer's weights again, so this is the unit the step is made of; the head,
the sampler and the cache write are outside it."""

from lib import looped_read


def read(run):
    scopes = looped_read.scope_seconds(run["work"].get("decode_program"))
    passes = looped_read.passes_per_step()
    if scopes is None or not passes:
        return None
    by_scope, runs = scopes
    return 1e3 * sum(by_scope.get(s, 0.0) for s in looped_read.PASS_SCOPES) / runs / passes
