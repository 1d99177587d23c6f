"""The one traffic generator: turns a traffic file and ``--seed`` into work.

A traffic file (``bench/traffic/<name>.json``) holds parameters only; its
``kind`` names the driver that runs it (``bench/drive_<kind>.py``):

* ``oneshot`` — a closed loop of one client asking for one-shot GA
  co-explorations back to back, as ``explore`` does.  Search ``i`` of a run
  takes the seed :func:`derive_seed` ``(seed, i)``, so a ``--seed`` gives
  the same searches in every run and no two searches share a seed.
"""

from __future__ import annotations

import hashlib


def derive_seed(seed: int, i: int) -> int:
    """A 63-bit seed for item ``i`` of the run seeded ``seed``."""
    digest = hashlib.sha256(f"{int(seed)}/{int(i)}".encode()).digest()
    return int.from_bytes(digest[:8], "big") >> 1
