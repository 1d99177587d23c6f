"""Import path of the scripts under ``bench/``: the checkout's root first
(the harness as the ``bench`` package), then ``src/`` (the program), and
not the scripts' own directory, which must shadow no other module.

A script started as ``python3 bench/<script>.py`` imports this module
before anything of the harness.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]
