"""The device's wait between two decode steps, and who owns it.

Between the end of one execution of the decode program and the start of
the next the device waits for the host: the tokens' way back, the
engine's loop, the next launch. ``lib/trace.reduce`` puts each such gap
down whole to whatever host span covers its midpoint, and
``lib/phases.idle_by_phase`` needs the two clocks lined up to a fraction
of a millisecond. This module differences each side on its OWN clock and
joins the two by order:

- **the device plane** (:func:`device_pairs`): the executions of the decode
  program on ``XLA Modules`` that lie whole inside the traced window, in
  order, and for each consecutive pair the wait ``start[i+1] - end[i]``
  with the other programs that ran between them and their device time.
  Programs under 50 us (a seed, a cast: a microsecond each, what a claim
  runs beside its prefill) are listed and do not count: a pair with no
  other program between is *plain*, a pair with one, the prefill, is a
  *claim* pair.
- **the ring** (:func:`ring_steps`): the program's four-wide ring
  ``(name, start_ns, duration_ns, cause)``, joined by cause: per decode
  step the start of ``gen.decode.put`` and ``gen.decode.dispatch``, the end
  of ``gen.decode.fetch``, its ``gen.emit``, and the ``gen.turn`` and the
  ``gen.admit`` entries that carry its id (they precede it).
- **the join** (:func:`joined_pairs`): execution i is the step whose
  dispatch phase began last before it. That lays the ring's dispatch
  starts on the trace's clock through the session's ``profile_start_time``,
  but only to tell neighbouring steps apart, some milliseconds where
  ``clock_check`` refuses a run over half of one: no reading below is a
  difference ACROSS the clocks, so none goes silent where the check
  refuses. Where the two sides do not hold the same steps (the counts
  differ by more than the span's two edges, or an execution finds no step
  of its own) the join is ``None``, said on stderr.

A program without ``gen.turn`` or causes (the parent of the PR that added
them) has no four-wide ring: what reads the ring returns ``None`` and
nothing raises; :func:`device_pairs` still reads its plane, but the
readers under ``metrics/`` leave all five out for such a program.
"""

import bisect
import statistics

from lib import phases, trace

PUT, DISPATCH, FETCH = "gen.decode.put", "gen.decode.dispatch", "gen.decode.fetch"
EMIT, TURN, ADMIT = "gen.emit", "gen.turn", "gen.admit"
#: how far before its dispatch phase an execution may seem to start: the
#: device plane runs up to 1.1 ms early a session (lib/phases.clock_check);
#: at most a quarter of the median time from one dispatch to the next
JOIN_SLACK_NS = 2_000_000
#: the two lists may differ by the steps the span's two edges cut
EDGE_STEPS = 2


def _say(message):
    phases._say(f"gap_read: {message}")


def program_caused_ring():
    """The program's four-wide ring, or ``None`` where it has none."""
    try:
        from deeplearning4j_tpu.obs.trace import caused_phases
    except ImportError:
        return None
    return [list(e) for e in caused_phases()]


_run = {}


def current():
    """``(xplane, four-wide ring)`` of this process's traced run; the
    trace is ``lib/phases.current``'s, parsed once."""
    if "ring" not in _run:
        _run["ring"] = program_caused_ring()
    return phases.current()[0], _run["ring"]


# ---------------------------------------------------------------------------
# (a) the device plane alone
# ---------------------------------------------------------------------------
def device_pairs(xplane, program):
    """``(runs, pairs)``: ``runs`` the (start, end) of the executions of
    ``program`` whole inside the traced window, first chip, in order;
    ``pairs[i]`` what lies between ``runs[i]`` and ``runs[i + 1]``:
    ``wait_ns``, ``between`` ([program, device ns] of every other
    execution that starts there), ``kind`` (``plain``: no program of
    50 us or more between; ``claim``: one, ``prefill_ns`` its time; else
    ``other``). ``None`` without a trace, a window or a
    program name."""
    if xplane is None or not program:
        return None
    planes = xplane["planes"]
    window, device = phases.window_of(planes), phases._first_device(planes)
    if window is None or device is None:
        return None
    lo, hi = window
    events = sorted((e[1], e[1] + e[2], trace.program_name(e[0]))
                    for e in phases._line(device, trace.MODULES_LINE))
    runs = [(a, b) for a, b, n in events if n == program and a >= lo and b <= hi]
    others = [(a, b, n) for a, b, n in events if n != program]
    starts = [o[0] for o in others]
    pairs = []
    for (_, end), (start, _) in zip(runs, runs[1:]):
        between = [[n, b - a] for a, b, n in
                   others[bisect.bisect_left(starts, end): bisect.bisect_left(starts, start)]]
        large = [ns for _, ns in between if ns >= trace.SHORT_GAP_NS]
        pair = {"wait_ns": start - end, "between": between,
                "kind": ("plain", "claim")[len(large)] if len(large) < 2 else "other"}
        if pair["kind"] == "claim":
            pair["prefill_ns"] = large[0]
        pairs.append(pair)
    return runs, pairs


# ---------------------------------------------------------------------------
# (b) the ring alone, joined by cause
# ---------------------------------------------------------------------------
def ring_steps(ring):
    """The decode steps the four-wide ring holds whole, by the start of
    their dispatch phase: ``{"cause", "put_ns", "dispatch",
    "fetch_end", "emit_ns", "turn_ns", "admits"}``; ``turn_ns`` is
    ``None`` for a step that no turn precedes (the first after an idle
    wait). A step that drafted has two puts: the first put and dispatch
    and the last fetch are its own. ``None`` where the ring is not
    four wide or no entry carries a cause."""
    if not ring or any(len(e) < 4 for e in ring):
        return None
    by_cause = {}
    for name, start, dur, cause in ring:
        if cause is not None and name in (PUT, DISPATCH, FETCH, EMIT, TURN, ADMIT):
            by_cause.setdefault(cause, {}).setdefault(name, []).append((start, dur))
    if not by_cause:
        return None
    steps = []
    for cause, got in by_cause.items():
        if not all(name in got for name in (PUT, DISPATCH, FETCH, EMIT)):
            continue  # cut by the ring's oldest edge, or still running
        put, fetch = min(got[PUT]), max(got[FETCH])
        steps.append({"cause": cause, "put_ns": put[1],
                      "dispatch": min(got[DISPATCH])[0], "fetch_end": fetch[0] + fetch[1],
                      "emit_ns": got[EMIT][0][1],
                      "turn_ns": got[TURN][0][1] if TURN in got else None,
                      "admits": len(got.get(ADMIT, ()))})
    return sorted(steps, key=lambda s: s["dispatch"])


def window_steps(xplane, steps):
    """The steps whose dispatch phase began inside the traced window (the
    ring's clock through ``profile_start_time``); all of them where the
    trace gives no window."""
    bounds = phases.window_on_ring_clock(xplane)
    if bounds is None:
        return steps
    return [s for s in steps if bounds[0] <= s["dispatch"] < bounds[1]]


# ---------------------------------------------------------------------------
# (c) the two, matched one to one by order
# ---------------------------------------------------------------------------
def joined_pairs(xplane, ring, program):
    """:func:`device_pairs`' pairs, each with what the loop did between
    the same two steps: ``host_turn_ns`` (the end of step i's fetch to the
    start of step i+1's dispatch: ``gen.emit`` + ``gen.turn`` +
    ``gen.decode.put``), ``emit_ns``, ``turn_ns``, ``put_ns``, ``admits``
    (claims inside the turn) and ``causes``. A pair whose two steps' ids
    do not follow one another keeps its device side only. ``None`` where
    either side has nothing to read or the two do not hold the same
    steps."""
    device, steps = device_pairs(xplane, program), ring_steps(ring)
    if device is None or steps is None:
        return None
    runs, pairs = device
    if xplane["start_ns"] is None:
        _say("the trace has no profile_start_time: the ring cannot be laid beside the plane")
        return None
    inside = window_steps(xplane, steps)
    if abs(len(inside) - len(runs)) > EDGE_STEPS:
        _say(f"the window holds {len(runs)} executions of {program} and {len(inside)} decode steps of the ring: "
             "not the same steps, nothing joined")
        return None
    dispatched = [s["dispatch"] - xplane["start_ns"] for s in steps]
    slack = JOIN_SLACK_NS
    if len(dispatched) > 1:
        slack = min(slack, statistics.median(b - a for a, b in zip(dispatched, dispatched[1:])) / 4)
    matched = [bisect.bisect_right(dispatched, a + slack) - 1 for a, _ in runs]
    if any(j < 0 for j in matched) or any(k - j != 1 for j, k in zip(matched, matched[1:])):
        _say(f"the {len(runs)} executions of {program} do not each follow a dispatch phase of their own "
             f"(steps {matched[:3]}...{matched[-3:]} of {len(steps)}): nothing joined")
        return None
    out = []
    for pair, j in zip(pairs, matched):
        before, after = steps[j], steps[j + 1]
        pair = dict(pair, causes=[before["cause"], after["cause"]])
        if after["cause"] == before["cause"] + 1:
            pair.update(host_turn_ns=after["dispatch"] - before["fetch_end"], emit_ns=before["emit_ns"],
                        turn_ns=after["turn_ns"], put_ns=after["put_ns"], admits=after["admits"])
        out.append(pair)
    return out


# ---------------------------------------------------------------------------
# the five numbers
# ---------------------------------------------------------------------------
def _median_ms(values):
    values = list(values)
    return statistics.median(values) * 1e-6 if values else None


def launch_gap_ms(pairs):
    """Median wait of the device over the plain pairs."""
    return _median_ms(p["wait_ns"] for p in pairs or () if p["kind"] == "plain")


def claim_gap_ms(pairs):
    """Median, over the claim pairs, of the wait less the prefill's own
    execution: the host time a claim adds to the gap it falls in."""
    return _median_ms(p["wait_ns"] - p["prefill_ns"] for p in pairs or () if p["kind"] == "claim")


def _plain_turns(joined):
    """The joined plain pairs: no program between on the device, no claim
    in the ring."""
    return [p for p in joined or () if p["kind"] == "plain" and p.get("admits") == 0]


def host_turn_ms(joined):
    return _median_ms(p["host_turn_ns"] for p in _plain_turns(joined))


def launch_gap_runtime_ms(joined):
    """Median, pair by pair, of the wait less the loop's own time: the
    device's last operation to the tokens on the host, and the dispatch's
    entry to the first operation."""
    return _median_ms(p["wait_ns"] - p["host_turn_ns"] for p in _plain_turns(joined))


def turn_ms(xplane, ring):
    """Median ``gen.turn`` of the window's steps with no claim inside,
    from the ring alone."""
    steps = ring_steps(ring)
    if steps is None or xplane is None:
        return None
    return _median_ms(s["turn_ns"] for s in window_steps(xplane, steps)
                      if s["turn_ns"] is not None and not s["admits"])


def summary(xplane, ring, program):
    """The five numbers of one run and how many pairs each rests on."""
    device = device_pairs(xplane, program)
    pairs = device[1] if device else None
    joined = joined_pairs(xplane, ring, program)
    kinds = [p["kind"] for p in pairs or ()]
    return {"launch_gap_ms.serve": launch_gap_ms(pairs), "host_turn_ms.serve": host_turn_ms(joined),
            "launch_gap_runtime_ms.serve": launch_gap_runtime_ms(joined),
            "turn_ms.serve": turn_ms(xplane, ring), "claim_gap_ms.serve": claim_gap_ms(pairs),
            "executions": len(device[0]) if device else 0,
            "pairs": {k: kinds.count(k) for k in ("plain", "claim", "other")},
            "joined_plain_pairs": len(_plain_turns(joined))}


# ---------------------------------------------------------------------------
# what the readers under metrics/ share: each reads this process's run
# ---------------------------------------------------------------------------
def run_device_pairs(run):
    """:func:`device_pairs`' pairs of this run; ``None`` too for a program
    whose ring carries no step id (the parent of the PR that added them):
    the five metrics are new together, though these pairs need no ring."""
    xplane, ring = current()
    device = device_pairs(xplane, run["work"].get("decode_program")) if ring_steps(ring) else None
    return device[1] if device else None


def run_joined_pairs(run):
    if "joined" not in _run:
        xplane, ring = current()
        _run["joined"] = joined_pairs(xplane, ring, run["work"].get("decode_program"))
    return _run["joined"]
