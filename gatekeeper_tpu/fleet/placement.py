"""Which chip a replica of a launch gets (docs/fleet.md, "Chips").

One replica, one chip: a chip belongs to one process at a time, and a
replica that opens every chip of its host keeps its neighbours off all
of them.  On Kubernetes the device plugin hands each pod its chip
(`google.com/tpu: "1"`); this module is for several replicas started on
ONE host by one launcher (`spawn_replica` / `spawn_fleet` /
`ReplicaSupervisor`, the benchmark's fleet role).

:func:`placement_env` is pure and this module imports neither ``jax``
nor anything that does: a launcher that has touched jax holds the chip
its children need.  How many chips the host has is the launcher's to
say (`chips`, an argument of the launch): it asks the device files
(`util/chips.py host_chips()`, as tools/check_fleet_chips.py does) or
is told (the benchmark's cell), and never asks jax.
"""

from __future__ import annotations

from typing import Dict

# what libtpu reads before it opens a chip (it is loaded by the child's
# first jax call, so the child's environment is the only place to say it)
VISIBLE_CHIPS = "TPU_VISIBLE_CHIPS"
_ONE_CHIP_ALONE = {
    # the process's slice of the host's chips is 1x1x1, and it is the
    # only process of its "pod slice": libtpu looks for no peers
    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
    "TPU_PROCESS_BOUNDS": "1,1,1",
}
# Not set: ALLOW_MULTIPLE_LIBTPU_LOAD.  On the chip machine four
# processes placed this way each loaded libtpu at once without it, and
# two given ONE chip are kept apart by the device file itself (the
# second fails at start-up, "open(/dev/vfio/1): Device or resource
# busy"); docs/fleet.md "Chips" has the readings.


def placement_env(index: int, chips: int) -> Dict[str, str]:
    """The environment that makes replica ``index`` of a launch on a
    host with ``chips`` chips open chip ``index % chips`` and no other.
    With one chip or none (the default everywhere) it is empty: the
    child's environment is the launcher's, as it always was."""
    if chips <= 1:
        return {}
    return {VISIBLE_CHIPS: str(index % chips), **_ONE_CHIP_ALONE}
