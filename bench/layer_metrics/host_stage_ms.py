"""Scan + staging (engine/scan.py, engine/reader.py): the profile's
read + merge + stage seconds, mean per statement, in ms. A host clock
round asynchronous dispatch: where the host waited on the walk executor.
The DQ and mesh executors do not charge these stages; a statement they
answer has nothing to read here."""


def read(run):
    staged = [sum(s["stages"].get(k, 0.0)
                  for k in ("read", "merge", "stage"))
              for s in run["statements"] if s.get("stages")]
    staged = [v for v in staged if v > 0]
    if not staged:
        return None
    return 1000.0 * sum(staged) / len(staged)
