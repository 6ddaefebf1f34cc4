"""What the ``looped_decoder_lm`` family's per-layer readers read: device
time under the scopes of a stack that runs several times
(``models/decoder_lm.py``: ``attn_full``, ``mlp`` and ``pass_close``, the
norm that closes each pass) and the engine's ``stack_passes`` counter.

``decoder_read.SCOPES`` is a constant of a file that belongs to cells that
exist, and its reduction (``scope_seconds``) looks names up in it; so the
reduction is borrowed with this module's list in its place for the call, as
``lib/ssm_read.py`` does. The counters are the ones the family leaves in
``decoder_read`` (``record``). On a program that has no ``pass_close`` scope
and no ``stack_passes`` counter (one whose stack runs once) every reader
here returns ``None``.
"""

from lib import decoder_read

PASS_SCOPES = ("attn_full", "mlp", "pass_close")
SCOPES = decoder_read.SCOPES + ("pass_close",)


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
    also where no operation of ``program`` lies under ``pass_close``."""
    read = with_scopes(decoder_read.scope_seconds, program) if program else None
    if read is None or "pass_close" not in read[0]:
        return None
    return read


def passes_per_step():
    """Passes over the stack a decode step of the traced span ran
    (``stack_passes`` over ``decode_steps``, both of the span); ``None``
    where the engine counts none."""
    passes = decoder_read.counter_delta("stack_passes", span=True)
    steps = decoder_read.counter_delta("decode_steps", span=True)
    return passes / steps if passes and steps else None
