#!/usr/bin/env python
"""Module-size lint: keep the runtime package decomposed.

The scheduler started life as one 1,700-line monolith and was split
into ``runtime/scheduler/`` (types / allocator / layouts / prefill /
prefix_pool / core) precisely so no single module re-accretes everything.
This lint is the ratchet: it fails the fast CI lane the moment any
module under ``src/repro/runtime/`` crosses the line budget, so growth
has to land as a new module (or a real refactor) instead of another
hundred lines on the biggest file.

Usage::

    python tools/check_module_size.py [--root src/repro/runtime] \
        [--limit 900] [-v]

Exits non-zero listing every offender; ``-v`` also prints the largest
modules while they still fit (the early-warning view).
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

DEFAULT_ROOT = "src/repro/runtime"
DEFAULT_LIMIT = 900


def measure(root: Path) -> list:
    """(lines, path) per python module under ``root``, largest first."""
    sizes = []
    for p in sorted(root.rglob("*.py")):
        with open(p, "rb") as fh:
            sizes.append((sum(1 for _ in fh), p))
    return sorted(sizes, reverse=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="fail if any runtime module exceeds the line budget")
    ap.add_argument("--root", default=DEFAULT_ROOT,
                    help=f"package directory to lint (default {DEFAULT_ROOT})")
    ap.add_argument("--limit", type=int, default=DEFAULT_LIMIT,
                    help="line budget per module (default "
                         f"{DEFAULT_LIMIT}; lower it to ratchet, never "
                         "raise it)")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="also print the largest in-budget modules")
    args = ap.parse_args(argv)
    root = Path(args.root)
    if not root.is_dir():
        print(f"check_module_size: no such directory: {root}",
              file=sys.stderr)
        return 2
    sizes = measure(root)
    over = [(n, p) for n, p in sizes if n > args.limit]
    for n, p in over:
        print(f"FAIL {p}: {n} lines > {args.limit} — split it "
              f"(see src/repro/runtime/scheduler/ for the shape)",
              file=sys.stderr)
    if args.verbose or over:
        shown = over if over else sizes[:5]
        if not over:
            for n, p in shown:
                print(f"  ok {p}: {n}/{args.limit} lines")
    if not over:
        top = sizes[0] if sizes else (0, root)
        print(f"check_module_size: {len(sizes)} modules under {root} "
              f"within {args.limit} lines (largest: {top[1]} at "
              f"{top[0]})")
    return 1 if over else 0


if __name__ == "__main__":
    sys.exit(main())
