"""Spread of a cell's end-to-end metrics over sets of runs, as the contract
measures it: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, for each
set, and the wider of them.  Beside it, each set's range (largest less
smallest over the median): the ledger's ``spread`` field has read nearer to
that than to the quartile distance, and one far-off run shows in it alone.

    python3 benchmark/tools/spread.py <set A's result files> -- <set B's>

A result file is a run's log: its last result line is read, whatever follows it.
"""

import json
import statistics
import sys


def last_line(path: str) -> dict:
    """The run's result: the last line of ``path`` that is a result object (a
    log that holds both streams ends in the ``check ...`` lines)."""
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith('{"correct"')]
    if not lines:
        raise SystemExit(f"{path}: no result line")
    return json.loads(lines[-1])


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def value_range(values) -> float:
    return (max(values) - min(values)) / statistics.median(values)


def trimmed(values) -> float:
    """A set's spread with its run farthest from the median left out: the
    check takes the mean of the two sets' for whether a bound is too tight
    (it may be at most half the bound), so one far-off run a set does no harm."""
    mid = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - mid))[:-1]
    return spread(kept)


def main() -> int:
    args, sets = sys.argv[1:], [[]]
    for a in args:
        if a == "--":
            sets.append([])
        else:
            sets[-1].append(last_line(a))
    names = sorted({k for s in sets for r in s for k in r["metrics"]})
    for name in names:
        rows = [[r["metrics"][name]["value"] for r in s if name in r["metrics"]] for s in sets]
        # set-up: each side's first run compiles and is left out
        rows = [v[1:] if name == "setup_s" else v for v in rows]
        spreads = [spread(v) for v in rows if len(v) >= 2]
        ranges = [value_range(v) for v in rows if len(v) >= 2]
        medians = [statistics.median(v) for v in rows if v]
        tight = [trimmed(v) for v in rows if len(v) >= 3]
        print(
            f"{name}: medians {[round(m, 4) for m in medians]} spreads "
            f"{[round(100 * s, 3) for s in spreads]}% (ranges "
            f"{[round(100 * s, 3) for s in ranges]}%) -> 5 x widest = "
            f"{round(500 * max(spreads), 2) if spreads else None}%; farthest run "
            f"left out, mean of sets = {round(100 * statistics.mean(tight), 3) if tight else None}%"
        )
    wrong = [r["seed"] for s in sets for r in s if not r["correct"]]
    print("runs", [len(s) for s in sets], "not correct:", wrong)
    return 0


if __name__ == "__main__":
    sys.exit(main())
