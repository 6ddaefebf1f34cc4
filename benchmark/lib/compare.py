"""The comparisons that decide ``correct``: each yields named numbers, and
every number has a limit of its own in the configuration file."""

import math
import statistics


def norm_gap(program, reference):
    """Worst leaf of |program's norm - reference's norm| over the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    if set(program) != set(reference):
        raise ValueError(f"leaves differ: {sorted(set(program) ^ set(reference))}")
    floor = statistics.median(reference.values())
    worst = max(reference, key=lambda k: abs(program[k] - reference[k]) / max(reference[k], floor))
    return abs(program[worst] - reference[worst]) / max(reference[worst], floor), worst


def train_numbers(program, reference, leaf_groups=None):
    """``program`` and ``reference``: ``losses`` (one per checked step, two or more),
    ``grad_norms`` (first step, by leaf), ``update_norms`` (over all the
    checked steps, by leaf). ``leaf_groups`` ({group: [leaf-name endings]},
    from the configuration) takes the two norm gaps once per group, as
    ``grad_norm_gap.<group>``: for a network whose gains and shifts are
    noisy by nature (batch norm), so that its weights can be held tightly.
    Every leaf has to fall into exactly one group."""
    names = list(reference["grad_norms"])
    if leaf_groups and any(sum(k.endswith(tuple(e)) for e in leaf_groups.values()) != 1
                           for k in names):
        raise ValueError("leaf_groups has to put every leaf into exactly one group")
    loss_gaps = [abs(p - r) / abs(r) if math.isfinite(p) else math.inf
                 for p, r in zip(program["losses"], reference["losses"])]
    # the first loss is taken at the seeded weights and hardly moves with
    # precision: it is held tightly against rows left out of the batch. The
    # later ones follow a trajectory that can amplify the arithmetic's noise
    numbers = {"loss_rel_gap.first": loss_gaps[0], "loss_rel_gap.later": max(loss_gaps[1:])}
    where = {}
    for group, endings in (leaf_groups or {"": None}).items():
        leaves = [k for k in names if endings is None or k.endswith(tuple(endings))]
        for what in ("grad", "update"):
            name = f"{what}_norm_gap" + (f".{group}" if group else "")
            numbers[name], where[name] = norm_gap(
                {k: program[what + "_norms"][k] for k in leaves},
                {k: reference[what + "_norms"][k] for k in leaves})
    return numbers, where


def judge(numbers, limits):
    """(all within their limits, one printable line per number). A number
    without a limit, or one that is not finite, fails."""
    ok, lines = True, []
    for name, value in numbers.items():
        limit = limits.get(name)
        within = limit is not None and math.isfinite(value) and value <= limit
        ok = ok and within
        lines.append({"compared": name, "value": value, "limit": limit, "within": within})
    return ok, lines
