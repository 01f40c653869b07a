"""Peak bytes in use on the fullest chip, in GB (``memory_stats()``)."""


def reduce(facts, args):
    peak = facts.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
