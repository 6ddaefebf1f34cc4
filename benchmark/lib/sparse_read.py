"""What the ``sparse_latent_decoder_lm`` family's per-layer readers read:
device time under the scopes of a latent layer with an indexer
(``models/decoder_lm.py``: ``attn_index_proj``, ``attn_index_score``,
``attn_index_select``, ``attn_sparse_core`` inside ``attn_latent_proj``) and
of the shared expert, beside those ``lib/decoder_read.py`` knows, and the two
counters of the selection (``index_positions_scored``,
``sparse_positions_read``) over the traced span.

``decoder_read.SCOPES`` is a constant of a file that belongs to cells that
exist, and its reduction (``scope_seconds``) looks names up in it; so the
reduction is borrowed with this module's list in its place for the call, as
``lib/latent_read.py`` does. The counters are the ones the family leaves in
``decoder_read`` (``record``). Where the program has no such scope or
counter (a parent commit), every reader here returns ``None``.
"""

from lib import decoder_read

INDEX_SCOPES = ("attn_index_proj", "attn_index_score", "attn_index_select")
SPARSE_SCOPES = INDEX_SCOPES + ("attn_sparse_core",)
SCOPES = decoder_read.SCOPES + ("attn_latent_proj", "moe_shared") + SPARSE_SCOPES


def with_scopes(call, *args):
    """``call(*args)`` with ``decoder_read`` looking names up in this
    module's scope list."""
    kept = decoder_read.SCOPES
    decoder_read.SCOPES = SCOPES
    try:
        return call(*args)
    finally:
        decoder_read.SCOPES = kept


def scope_of(op_name):
    return with_scopes(decoder_read.scope_of, op_name)


def scope_seconds(program):
    """As ``decoder_read.scope_seconds`` with this module's scopes; ``None``
    also where no operation of ``program`` carries a scope of the selection
    (a program that has none, or one compiled before they were added)."""
    read = with_scopes(decoder_read.scope_seconds, program) if program else None
    if read is None or not any(s in read[0] for s in SPARSE_SCOPES):
        return None
    return read


def scope_ms(scopes, program):
    """Device self milliseconds under ``scopes`` per execution of ``program``."""
    read = scope_seconds(program)
    if read is None:
        return None
    by_scope, runs = read
    return 1e3 * sum(by_scope.get(s, 0.0) for s in scopes) / runs


def roofline_share(run, counter, work_key, scopes):
    """100 x the least seconds for the traced span's ``counter`` positions of
    ``run["work"][work_key]`` (``lib/work_sparse.least_seconds``) over the
    device seconds under ``scopes`` in the decode program. No clamp."""
    from lib import work_sparse

    w = run["work"]
    positions = decoder_read.counter_delta(counter, span=True)
    read = scope_seconds(w.get("decode_program")) if work_key in w else None
    seconds = sum(read[0].get(s, 0.0) for s in scopes) if read else 0.0
    if not positions or seconds <= 0:
        return None
    return 100.0 * work_sparse.least_seconds(positions, w[work_key], run["peaks"]) / seconds
