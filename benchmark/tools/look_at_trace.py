#!/usr/bin/env python3
"""Print the planes, lines and most frequent event names of a trace."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import xplane  # noqa: E402

if __name__ == "__main__":
    path = sys.argv[1]
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    print(path)
    print(xplane.describe(path, max_lines=int(sys.argv[2])
                          if len(sys.argv) > 2 else 200))
