"""Per-layer metrics read from the program's always-on totals
(``deepspeed_tpu.monitor.trace.tracer.totals``) as they stand when the result
line is written. That is exact for what is over before traffic — the stages
of set-up (``setup/*``) and what was traced, lowered and compiled in them
(``compile/build/*``, ``compile/warmup/*``) — and for what is counted to the
end of the process (``compile/traffic/*``); it cannot give a window's share of
a counter. A counter the program did not write reads 0.0, not nothing: a
program older than the counter gives the same line with zeros."""


def read(view, names, scale=1.0):
    """The sum of the totals called ``names``, scaled."""
    from deepspeed_tpu.monitor.trace import tracer
    totals = dict(tracer.totals)
    return scale * sum(totals.get(name, 0.0) for name in names)


def share_of_setup(view, names):
    """That sum over the run's ``setup_s``, in percent."""
    setup_s = view["values"].get("setup_s")
    return 100.0 * read(view, names) / setup_s if setup_s else 0.0
