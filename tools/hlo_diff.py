#!/usr/bin/env python
"""Diff two compiled programs' HLO texts (``compiled.as_text()``) with their
metadata stripped (``repro.obs.trace.strip_metadata``): op names, source
locations, the Pallas kernels' names and the instruction numbering.

    PYTHONPATH=src python tools/hlo_diff.py parent.txt[.gz] change.txt[.gz]

Prints the unified diff and exits 1 where the programs differ; prints
nothing and exits 0 where scopes were all that changed.
"""
from __future__ import annotations

import difflib
import gzip
import sys

from repro.obs.trace import strip_metadata


def read(path: str) -> str:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return f.read()


def main(argv) -> int:
    a, b = (strip_metadata(read(p)).splitlines() for p in argv)
    diff = list(difflib.unified_diff(a, b, argv[0], argv[1], lineterm=""))
    print("\n".join(diff))
    return 1 if diff else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:3]))
