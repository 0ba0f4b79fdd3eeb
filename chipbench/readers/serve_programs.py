"""Shares of the serving programs' device time for a cell whose two-second
capture may hold none of the programs asked for."""

from chipbench.reduce import named, xplane


def program_share(view, prefixes):
    """``readers/named.py``'s ``program_share``, in percent, but 0 (not
    nothing) where the device worked and no program under ``prefixes`` ran:
    in a closed loop whose requests decode for 4-17 s, two seconds can pass
    without one prefill pass, and the prefill programs' share of that
    capture is then nought, not unknown. Nothing, still, from a trace in
    which no operation ran. (Were the programs renamed this would read 0;
    the cell's ``decode_step_ms``, read by name through ``named.py``, would
    then be missing and say so.)"""
    share = named.program_share(view["trace"], prefixes)
    if share is None and any(xplane.covered_ns(xplane.union(dev.ops))
                             for dev in view["trace"].devices.values()):
        share = 0.0
    return None if share is None else 100.0 * share
