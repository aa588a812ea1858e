"""Share of CF factor-row lookups in the window served by the hot-row
replica, from the head's hit and miss counters, percent."""


def read(run):
    hits = run.counters.get("cf.hits", 0)
    total = hits + run.counters.get("cf.misses", 0)
    return 100.0 * hits / total if total else None
