"""Mean of the samples under ``key``, times ``scale``."""


def read(record, trace, cell, key, scale=1.0):
    values = record.get(key)
    if not values:
        return None
    return scale * sum(values) / len(values)
