"""Reduce one trace directory to JSON (a process of its own, with JAX
held to the CPU: the parent of a run never imports JAX)."""

from __future__ import annotations

import json
import sys


def main() -> None:
    from benchmarks import trace
    path = trace.find_xplane(sys.argv[1])
    if path is None:
        sys.exit(f"no .xplane.pb under {sys.argv[1]}")
    out = trace.reduce_xplane(path)
    out["file"] = path
    with open(sys.argv[2], "w") as f:
        json.dump(out, f)


if __name__ == "__main__":
    main()
