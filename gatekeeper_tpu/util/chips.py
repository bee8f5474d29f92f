"""The host's TPU chips as its device files show them: how many there
are (a launcher asks here, never jax: fleet/placement.py) and which a
process holds (parallel/mesh.py chip_info).  Imports nothing of jax."""

from __future__ import annotations

import os
import re
from typing import List

# a chip is one device file: /dev/accel<N> (N the chip's index), or
# /dev/vfio/<N> on hosts that pass the chips through (N the IOMMU group
# the chip is passed through as: one a chip, so distinct numbers are
# distinct chips, but not the index TPU_VISIBLE_CHIPS counts in)
_CHIP_FILE = re.compile(r"^/dev/(?:accel|vfio/)(\d+)$")


def host_chips() -> int:
    """How many chips this host has: its chip device files, counted
    (0 off a TPU).  What a launcher passes as ``chips``."""
    n = 0
    for d in ("/dev", "/dev/vfio"):
        try:
            names = os.listdir(d)
        except OSError:
            continue
        n += sum(1 for name in names if _CHIP_FILE.match(f"{d}/{name}"))
    return n


def held_chips(pid: str = "self") -> List[int]:
    """The chips a process HOLDS: the numbers of the chip device files
    it has open, sorted (the device file's number, as above: a label
    that tells two chips of one host apart, not a libtpu index).  What a replica got, whatever it was asked to
    take: under TPU_VISIBLE_CHIPS every process calls its one device
    id 0 at coordinates (0,0,0), so jax cannot tell two replicas'
    chips apart and the open file can.  Empty before the backend is up
    and off a TPU."""
    held = set()
    try:
        fds = os.listdir(f"/proc/{pid}/fd")
    except OSError:
        return []
    for fd in fds:
        try:
            m = _CHIP_FILE.match(os.readlink(f"/proc/{pid}/fd/{fd}"))
        except OSError:
            continue  # closed between the listing and the read
        if m:
            held.add(int(m.group(1)))
    return sorted(held)
