import json, sys
for path in sys.argv[1:]:
    try:
        lines = open(path).read().strip().splitlines()
        r = json.loads(lines[-1])
    except Exception as e:
        print(path, "NO RESULT", e); continue
    print(path, "correct", r["correct"], "attempted", r["attempted"], "device", r["device"])
    print("  metrics", {k: round(v["value"], 4) for k, v in r["metrics"].items()})
    print("  cmp", {k: (round(v[0], 6), v[1]) for k, v in r["comparisons"].items()})
    print("  setup", r.get("setup_spans_s"))
    if "device_kinds_s" in r:
        print("  kinds", [(k, round(v, 4)) for k, v in r["device_kinds_s"]])
    if "breakdown" in r:
        for n, s in r["breakdown"]["device_ops"]: print("   op", round(s, 4), n[:110])
        print("   gaps", [(n, round(s, 4)) for n, s in r["breakdown"]["idle_gaps"]])
