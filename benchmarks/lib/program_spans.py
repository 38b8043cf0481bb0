"""Per-epoch arithmetic on the program's own spans.

``znicz_tpu.core.telemetry.spans()`` gives the ring's complete spans as
``(name, start_ns, dur_ns, id, parent, attrs)`` on ``time.perf_counter_ns()``
and ``spans("i")`` its instant markers in the same shape; the loader drops
a ``loader.epoch_end`` marker as it serves an epoch's last minibatch.
Everything here is arithmetic on those two lists, tested on a recorded
span list without a chip (``tests/test_program_spans.py``).

The timed window is whole epochs, cut where the decision ends an epoch;
the markers fall one window earlier (the last minibatch is served while the
epoch's last train window is collected).  ``cut`` keeps the last
``n_epochs`` marker-to-marker intervals, which are therefore shifted
against the timed window by less than one window; a per-epoch mean in
steady state does not see that.  A span belongs to the interval it starts
in; self times are the program's own arithmetic (``telemetry.self_times``),
taken on the whole ring first, so that a span cut off from its children by
a marker keeps them out of its self time.
"""

MARKER = "loader.epoch_end"


def cut(spans, markers, n_epochs):
    """(spans that start inside, lo, hi) for the last ``n_epochs``
    marker-to-marker intervals, or None where the ring holds fewer."""
    stamps = sorted(m[1] for m in markers if m[0] == MARKER)
    if n_epochs < 1 or len(stamps) < n_epochs + 1:
        return None
    lo, hi = stamps[-(n_epochs + 1)], stamps[-1]
    return [s for s in spans if lo <= s[1] < hi], lo, hi


def total_ms(spans, wanted, n_epochs, own=None):
    """Milliseconds an epoch of the spans ``wanted(span)`` picks: their
    durations, or their self times where ``own``
    (``telemetry.self_times``'s result) is given.  None where no span is
    picked."""
    picked = [s for s in spans if wanted(s)]
    if not picked:
        return None
    if own is None:
        ns = sum(s[2] for s in picked)
    else:
        ns = sum(own[s[3]] for s in picked)
    return ns / 1e6 / n_epochs


def unspanned_ns(spans, lo, hi):
    """Time of [lo, hi] that no leaf span of the ring covers: what the host
    did that no span names (the scheduler between units, a unit's own work
    around its children)."""
    parents = {s[4] for s in spans}
    covered = 0
    end = lo
    for start, stop in sorted((s[1], s[1] + s[2]) for s in spans
                              if s[3] not in parents
                              and s[1] < hi and s[1] + s[2] > lo):
        start, stop = max(start, end), min(stop, hi)
        if stop > start:
            covered += stop - start
            end = stop
    return (hi - lo) - covered


def ring(ph="X"):
    """The program's complete spans (``ph="i"``: its instant markers), or
    None where the program has no such reader (a commit before PR 25)."""
    from znicz_tpu.core import telemetry
    if not hasattr(telemetry, "spans"):
        return None
    return telemetry.spans(ph)


_MEMO = {}


def of_run(ctx):
    """``{"ring", "spans", "own", "lo", "hi", "n"}`` of a run's timed
    epochs, from the program's ring (``spans``: those of the epochs kept;
    ``own``: self times of the whole ring); None where the program records
    no such spans (a commit before PR 25) or the ring holds too few
    epochs."""
    whole = ring()
    if whole is None:
        return None
    key = id(ctx["epoch_times"])
    if key not in _MEMO:
        from znicz_tpu.core import telemetry
        kept = cut(whole, ring("i"), ctx["epochs"])
        if kept is not None:
            spans, lo, hi = kept
            kept = {"ring": whole, "spans": spans,
                    "own": telemetry.self_times(whole),
                    "lo": lo, "hi": hi, "n": ctx["epochs"]}
        _MEMO.clear()
        _MEMO[key] = kept
    return _MEMO[key]


def named(*names):
    return lambda span: span[0] in names


def under(ring, name, parent):
    """Picks the spans ``name`` whose parent span is a ``parent``: one name
    stands at several call sites (``trainer.readback`` ends a segment's
    last window and a validation minibatch)."""
    parents = {s[3] for s in ring if s[0] == parent}
    return lambda span: span[0] == name and span[4] in parents
