"""Per-layer metrics read from the device trace by the names the program gave
its work (``chipbench/reduce/named.py``): programs by name, operations by
``jax.named_scope``. A reader that needs the operations' names (carried by a
``--trace 2`` capture as ``view["op_names"]``) returns nothing without them,
and so does any reader that finds nothing under its name: the metric is then
absent, not wrong. Shares are given in percent."""

from chipbench.reduce import flash_flops, named


def _percent(share):
    return None if share is None else 100.0 * share


def program_ms(view, prefix):
    return named.program_ms(view["trace"], prefix)


def program_share(view, prefixes):
    return _percent(named.program_share(view["trace"], prefixes))


def scope_share(view, scope, instructions=()):
    if not view.get("op_names"):
        return None
    return _percent(named.scope_share(view["trace"], view["op_names"], scope,
                                      instructions))


def hidden_collective_share(view):
    return _percent(named.hidden_collective_share(view["trace"]))


def flash_roofline_share(view):
    """FLOPs the flash kernels executed under the causal mask over their
    device time, over the chip's bf16 peak. Each call's shapes are read from
    its own HLO text."""
    if not view.get("op_names"):
        return None
    flops = ns = 0.0
    for kernel in flash_flops.MATMULS:
        for ev, t in named.scope_calls(view["trace"], view["op_names"],
                                       kernel):
            shape = named.first_operand_shape(ev.name)
            if shape is None:
                return None
            flops += flash_flops.call_flops(kernel, *shape)
            ns += t
    if not ns:
        return None
    return 100.0 * flops / (ns * 1e-9) / view["peaks"]["bf16_flops_per_s"]
