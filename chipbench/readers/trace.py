"""Per-layer metrics read from the reduced device trace
(``chipbench/reduce/xplane.py``). Shares are given in percent."""

from chipbench.reduce import xplane

_PICK = {"mosaic": xplane.is_mosaic, "collective": xplane.is_collective,
         "any": lambda name: True}


def idle_share(view):
    return 100.0 * xplane.idle_share(view["trace"])


def module_ms(view, min_mean_ms=0.0):
    return xplane.module_ms(view["trace"], min_mean_ms=min_mean_ms)


def op_share(view, kind="any", prefix=None, exclude_prefix=None):
    """Self time of the operations of ``kind`` (``mosaic``, ``collective``
    or ``any``) whose instruction name starts with ``prefix`` and with none
    of ``exclude_prefix``, over device busy time."""
    def pick(name):
        instr = xplane.instruction(name)
        return (_PICK[kind](name)
                and (prefix is None or instr.startswith(prefix))
                and not any(instr.startswith(p) for p in exclude_prefix or ()))
    return 100.0 * xplane.op_share(view["trace"], pick)


def exposed_collective_share(view):
    return 100.0 * xplane.exposed_collective_share(view["trace"])
