"""The repo's benchmark: see README.md beside this file."""

import sys


def log(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
