"""Per-layer metrics read from the program's own spans, which a ``--trace 2``
capture returns on the trace's clock (``view["capture"]``, a
``deepspeed_tpu.monitor.trace.Capture``). Without a capture (``--trace 0``
and ``1``) every reader returns nothing. Shares are given in percent.

A thread's phase spans are recorded when a phase ends, and only if tracing
was on when it began: the phase a capture starts in and the one it stops in
are missing from it (in cell 1 a decode slice of 170 ms at either end of
2 s). So a share of a thread's time is taken over the extent its whole
phases cover (``within``), from the first one's start to the last one's end,
not over the captured interval."""

from chipbench.reduce import xplane


def _covered_ns(spans):
    return xplane.covered_ns(xplane.union(
        xplane.Event(n, a, b - a) for n, a, b in spans))


def share(view, names, within=None):
    """Time inside the spans called one of ``names`` over the extent of the
    spans called one of ``within`` (over the captured interval without)."""
    capture = view.get("capture")
    if capture is None:
        return None
    spans = capture.spans(*names)
    if within is None:
        lo, hi = capture.start_ns, capture.stop_ns
    else:
        frame = capture.spans(*within)
        if not frame:
            return None
        lo, hi = frame[0][1], max(b for _, _, b in frame)
        spans = [(n, max(a, lo), min(b, hi)) for n, a, b in spans
                 if min(b, hi) > max(a, lo)]
    if hi <= lo or (not spans and within is None):
        return None
    return 100.0 * _covered_ns(spans) / (hi - lo)


def unaccounted_share(view, names):
    """What the spans called ``names`` leave uncovered of their own extent."""
    covered = share(view, names, within=names)
    return None if covered is None else 100.0 - covered


def ms_per(view, names, per):
    """Milliseconds inside the spans called ``names`` for each span called
    ``per``; only ``per`` spans that lie whole inside the interval count,
    with the ``names`` spans inside them."""
    capture = view.get("capture")
    if capture is None:
        return None
    whole = [(r[2], r[3]) for r in capture.records
             if r[0] == "X" and r[1] == per
             and r[2] >= capture.start_ns and r[3] <= capture.stop_ns]
    if not whole:
        return None
    total = 0.0
    for _, a, b in capture.spans(*names):
        if any(wa <= a and b <= wb for wa, wb in whole):
            total += b - a
    return total * 1e-6 / len(whole)
