"""Handing each engine process its own TPU chip.

A chip belongs to one process at a time, and a process that initialises JAX
takes every chip libtpu shows it. A launcher that starts several engine
processes on one host therefore has to narrow what each child sees, through
the environment libtpu reads at load (libtpu 0.0.34: ``TPU_VISIBLE_CHIPS``,
``TPU_CHIPS_PER_PROCESS_BOUNDS``, ``TPU_PROCESS_BOUNDS``), and must itself
stay off JAX. This module imports nothing from JAX for that reason.
"""

from __future__ import annotations

import glob
from typing import Dict


def local_chip_count() -> int:
    """TPU chips on this host, counted from their device nodes without
    loading libtpu (``/dev/accel*`` or, on vfio hosts such as v5e,
    ``/dev/vfio/<n>``). 0 = no chip: engine children run where JAX puts
    them (the CPU route of the tests)."""
    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )


def chip_env(index: int) -> Dict[str, str]:
    """Environment that shows a child process exactly chip ``index`` as a
    one-chip slice of its own."""
    return {
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def place_workers(n_workers: int) -> list:
    """One environment per engine process, each with a distinct chip; empty
    environments on a host without chips. Fails loudly when there are more
    engine processes than chips — running the extra ones on the CPU would
    hide the fault behind a server that answers."""
    chips = local_chip_count()
    if chips == 0:
        return [{} for _ in range(n_workers)]
    if n_workers > chips:
        raise SystemExit(
            f"{n_workers} engine processes need {n_workers} chips; this "
            f"host has {chips}. A chip serves one process at a time."
        )
    return [chip_env(i) for i in range(n_workers)]
