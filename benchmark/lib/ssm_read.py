"""What the ``hybrid_decoder_lm`` family's per-layer readers read: device time
under the scopes of a state-space layer (``models/decoder_lm.py``:
``ssm_proj``, ``ssm_conv``, ``ssm_scan``; ``state_write`` in prefill) and of
the shared expert, beside those ``lib/decoder_read.py`` knows.

``decoder_read.SCOPES`` is a constant of a file that belongs to cells that
exist, and its reduction (``scope_seconds``) looks names up in it; so the
reduction is borrowed with this module's list in its place for the call, as
``lib/latent_read.py`` does. The counters are the ones the family leaves in
``decoder_read`` (``record``): ``state_slots`` among them.
"""

from lib import decoder_read

SSM_SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan")
SCOPES = decoder_read.SCOPES + SSM_SCOPES + ("state_write", "moe_shared")


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
    also where no operation of ``program`` carries a state-space scope (a
    program that has none, or one compiled before they were added)."""
    read = with_scopes(decoder_read.scope_seconds, program) if program else None
    if read is None or not any(s in read[0] for s in SSM_SCOPES):
        return None
    return read


def scope_ms(scopes, program):
    """Device self milliseconds under ``scopes`` per execution of ``program``."""
    read = scope_seconds(program)
    if read is None:
        return None
    by_scope, runs = read
    return 1e3 * sum(by_scope.get(s, 0.0) for s in scopes) / runs
