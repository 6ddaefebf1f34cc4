"""Device milliseconds a decode step spends under the ``sample`` scope
(the in-graph sampler: argmax alone where every slot is greedy, the
sorts, gathers and the draw where a slot asks for them): self time
inside the decode program's executions of the traced window over their
number.

Left out where the trace shows no scope of the program's at all: it has
none, or its executable was compiled before they were added and came out
of the persistent cache, whose key leaves names out (said on stderr)."""

from lib import phases


def read(run):
    program = run["work"].get("decode_program")
    return phases.run_scope_ms("sample", program) if program else None
