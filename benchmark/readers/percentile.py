"""Nearest-rank percentile ``q`` of the samples under ``key`` (q=50 with an
even count takes the lower middle); nothing to read when there are none."""

from harness import stats


def read(record, trace, cell, key, q):
    values = record.get(key)
    if not values:
        return None
    return stats.percentile(values, q)
