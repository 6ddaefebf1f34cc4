"""Share of device-busy time under the ``attn`` scope (projections,
scores, softmax, the flash custom calls, output projection, forward and
backward), over the traced window.

Left out where the trace shows no scope of the program's at all: it has
none, or its executable was compiled before they were added and came out
of the persistent cache, whose key leaves names out (said on stderr)."""

from lib import phases


def read(run):
    return phases.run_scope_share(("attn",))
