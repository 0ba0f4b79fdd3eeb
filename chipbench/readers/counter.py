"""A number the driver counted (``Outcome.counters``), as it is or scaled."""


def read(view, name, scale=1.0):
    value = view["counters"].get(name)
    return None if value is None else scale * value
