"""A number the run record already holds: ``key`` times ``scale``."""


def read(record, trace, cell, key, scale=1.0):
    value = record.get(key)
    return None if value is None else value * scale
