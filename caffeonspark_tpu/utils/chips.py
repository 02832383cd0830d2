"""How many TPU chips this host has — asked WITHOUT touching JAX.

A chip belongs to one process at a time: the first process to
initialise the JAX TPU backend claims every chip it can see, and a
second process that needs one then fails or hangs in its warm-up.  A
parent that is about to start chip-holding children (the serving
fleet, the deploy loop) must therefore count chips before anyone —
itself included — initialises a backend, which rules out
`jax.devices()`.  The count comes from the environment and the device
nodes libtpu itself opens.
"""

from __future__ import annotations

import glob
import os
import sys
from typing import Dict, List


def local_chip_ids() -> List[str]:
    """Ids of the TPU chips a process started here would see; empty
    when this is not a TPU host or JAX is held to another platform
    (JAX_PLATFORMS)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.lower().split(","):
        return []
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible is not None:
        return [c.strip() for c in visible.split(",") if c.strip()]
    # v5e and later expose one vfio group per chip (next to the
    # /dev/vfio/vfio control node); earlier generations /dev/accelN
    nodes = len(glob.glob("/dev/vfio/[0-9]*")) \
        + len(glob.glob("/dev/accel[0-9]*"))
    return [str(i) for i in range(nodes)]


def local_tpu_chips() -> int:
    """How many chips `local_chip_ids` finds (0 = no limit applies)."""
    return len(local_chip_ids())


def chip_env(chip: str) -> Dict[str, str]:
    """Environment that hands a child process exactly ONE chip.  Four
    children started this way ran side by side on a four-chip v5e host
    while the parent stayed off JAX (chip run, PR 21)."""
    return {"TPU_VISIBLE_CHIPS": chip,
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def this_process_holds_chips() -> bool:
    """Has this process already initialised the JAX TPU backend (and
    with it claimed the chips)?  Asked without initialising one."""
    if "jax" not in sys.modules:
        return False
    import jax
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized() \
        and jax.default_backend() == "tpu"


def require_chips(processes: int, what: str) -> List[str]:
    """Refuse, before anything is spawned, to start more chip-holding
    processes than the host has chips — or any at all from a process
    that holds the chips itself.  Returns the host's chip ids (empty:
    not a TPU host, nothing to hand out)."""
    ids = local_chip_ids()
    chips = len(ids)
    if chips and this_process_holds_chips():
        raise RuntimeError(
            f"{what} needs child processes that each hold a TPU chip, "
            "but this process has initialised the JAX TPU backend and "
            "holds every chip of the host itself: a chip belongs to "
            "one process at a time — start the children from a process "
            "that has not touched JAX (ROADMAP queue 3 item 4)")
    if chips and processes > chips:
        raise RuntimeError(
            f"{what} needs {processes} processes that each hold a TPU "
            f"chip, but this host has {chips}: a chip belongs to one "
            "process at a time, so the extra ones would fail or hang "
            "in warm-up (serve over several chips in ONE process with "
            "-serveMesh; ROADMAP queue 3 item 4)")
    return ids
