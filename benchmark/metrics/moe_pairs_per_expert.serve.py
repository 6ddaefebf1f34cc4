"""(token, expert) pairs computed here over held experts that got one, in
the window's decode steps: the rows a grouped product has an expert's
weights for. The deployment this chip stands for gives each expert the
requests of all its chips (about 25 pairs at 50 busy slots a chip); one
chip's own requests give it one or two."""

from lib import decoder_read


def read(run):
    pairs = decoder_read.counter_delta("moe_pairs_local")
    hit = decoder_read.counter_delta("moe_experts_hit")
    return pairs / hit if pairs is not None and hit else None
