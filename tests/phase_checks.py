"""What the phase tests share: checks on ring entries ``(name, start_ns,
duration_ns)`` of ``deeplearning4j_tpu.obs.trace``."""


def covered_ns(entries, lo, hi):
    """Length of the union of the entries' intervals inside [lo, hi]."""
    total, end = 0, lo
    for a, b in sorted((s, s + d) for _, s, d in entries):
        a, b = max(a, end), min(b, hi)
        if b > a:
            total += b - a
            end = b
    return total


def assert_nested_or_disjoint(entries):
    """Phases of one thread nest or follow one another; two that overlap
    in part would count some host time twice."""
    open_ends = []
    for name, a, d in sorted(entries, key=lambda e: (e[1], -e[2])):
        while open_ends and open_ends[-1][1] <= a:
            open_ends.pop()
        assert not open_ends or a + d <= open_ends[-1][1], (
            f"{name} [{a}, {a + d}] overlaps {open_ends[-1][0]} in part")
        open_ends.append((name, a + d))


def scopes_in(lowered_text):
    """The path components of every operation name in a lowered program's
    text (``Lowered.as_text(debug_info=True)``), ``transpose(jvp(x))``
    read as ``x``: a ``jax.named_scope`` shows as one of them."""
    import re

    found = set()
    for name in re.findall(r'loc\("([^"]*)"', lowered_text):
        for part in name.split("/"):
            found.add(re.sub(r"^(?:\w+\()*|\)*$", "", part))
    return found
