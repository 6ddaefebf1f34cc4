"""What the ``parallel_hybrid_decoder_lm`` family's per-layer readers read:
device time under the scopes of a PARALLEL block (``models/decoder_lm.py``:
``mixer_join``, the one norm before the two branches and their sum's way into
the residual; ``attn_full``, the attention branch; ``ssm_proj``, ``ssm_conv``
and ``ssm_scan``, the state-space branch; ``state_write`` in prefill) and the
engine's ``state_slots`` and ``attn_positions_read`` counters, which both
count for the one segment.

``decoder_read.SCOPES`` is a constant of a file that belongs to cells that
exist, and its reduction (``scope_seconds``) looks names up in it; so the
reduction is borrowed with this module's list in its place for the call, as
``lib/ssm_read.py`` does. The counters are the ones the family leaves in
``decoder_read`` (``record``). On a program that has no ``mixer_join`` scope
(one without a parallel block, or one compiled before the scope was added)
every reader here returns ``None``.
"""

import json
import sys

from lib import decoder_read

#: the two mixers of a block and the join, by scope
MIXER_SCOPES = ("mixer_join", "attn_full", "ssm_proj", "ssm_conv", "ssm_scan")
SCOPES = decoder_read.SCOPES + ("ssm_proj", "ssm_conv", "ssm_scan", "state_write", "mixer_join")


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
    also where no operation of ``program`` lies under ``mixer_join``."""
    read = with_scopes(decoder_read.scope_seconds, program) if program else None
    if read is None or "mixer_join" not in read[0]:
        return None
    return read


def scope_ms(scopes, program):
    """Device self milliseconds under ``scopes`` per execution of ``program``."""
    read = scope_seconds(program)
    if read is None:
        return None
    by_scope, runs = read
    return 1e3 * sum(by_scope.get(s, 0.0) for s in scopes) / runs


def span_cache_bytes(work):
    """Bytes of cache the decode steps of the traced span had to move: the
    live slots' state and tail read and written (``state_slots`` of the span
    x the bytes of one live slot through all layers) and the keys and values
    of the positions behind them read (``attn_positions_read`` x a
    position's bytes through all layers); (bytes, live slot-steps) or
    ``None`` where the engine counted neither. stderr gets the two counts,
    so that a share's numerator can be checked by hand."""
    slots = decoder_read.counter_delta("state_slots", span=True)
    positions = decoder_read.counter_delta("attn_positions_read", span=True)
    if not slots or positions is None:
        return None
    print(json.dumps({"parallel_read": {"span_state_slots": slots,
                                        "span_attn_positions_read": positions}}), file=sys.stderr)
    return (slots * work["state_bytes_per_live_slot"]
            + positions * work["cache_bytes_per_position"]), slots
