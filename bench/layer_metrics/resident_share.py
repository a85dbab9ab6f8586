"""HBM tiers (engine/resident.py): resident hits over hits + misses
during the window, from ResidentStore.snapshot() deltas, in %."""


def read(run):
    d = run["resident_delta"]
    looked = d["hits"] + d["misses"]
    if not looked:
        return None
    return 100.0 * d["hits"] / looked
