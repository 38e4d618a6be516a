"""``num`` over the product of ``den`` keys, times ``scale``; a key that
holds a list counts as its sum (a share of the window is sum / window)."""


def _value(record, key):
    v = record.get(key)
    if v is None:
        return None
    return float(sum(v)) if isinstance(v, (list, tuple)) else float(v)


def read(record, trace, cell, num, den, scale=1.0):
    top = _value(record, num)
    bottom = 1.0
    for key in den:
        part = _value(record, key)
        if part is None:
            return None
        bottom *= part
    if top is None or bottom == 0:
        return None
    return scale * top / bottom
