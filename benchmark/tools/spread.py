"""Spread of a cell's end-to-end metrics over sets of runs, as the contract
measures it: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, for each
set, and the wider of them.

    python3 benchmark/tools/spread.py <set A's result files> -- <set B's>
"""

import json
import statistics
import sys


def last_line(path: str) -> dict:
    with open(path) as f:
        return json.loads(f.read().strip().splitlines()[-1])


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


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
        medians = [statistics.median(v) for v in rows if v]
        print(
            f"{name}: medians {[round(m, 4) for m in medians]} spreads "
            f"{[round(100 * s, 3) for s in spreads]}% -> 5 x widest = "
            f"{round(500 * max(spreads), 2) if spreads else None}%"
        )
    wrong = [r["seed"] for s in sets for r in s if not r["correct"]]
    print("runs", [len(s) for s in sets], "not correct:", wrong)
    return 0


if __name__ == "__main__":
    sys.exit(main())
