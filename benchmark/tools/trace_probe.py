#!/usr/bin/env python3
"""Look at a trace by hand: planes, lines, event counts, first events,
then the reductions. `python benchmark/tools/trace_probe.py <dir|file>`
"""
from __future__ import annotations

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

from harness import xplane  # noqa: E402


def main() -> int:
    path = sys.argv[1]
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    if not path:
        print(json.dumps({"error": "no .xplane.pb found"}))
        return 1
    tr = xplane.load(path)
    print(json.dumps({"file": path, "bytes": os.path.getsize(path)}))
    for row in xplane.describe(tr, n=int(os.environ.get("PROBE_N", 4))):
        print(json.dumps(row)[:1500])
    b = xplane.busy(tr)
    b.pop("merged", None)
    print(json.dumps({"busy": b}))
    print(json.dumps({"breakdown": xplane.breakdown(tr)}))
    print(json.dumps({"collectives": xplane.collective_exposed(tr)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
