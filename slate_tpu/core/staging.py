"""Reused host staging buffers for host-to-device copies (PR 26; moved
here from linalg/stream.py in PR 28, when the mesh's placement became
their second user).

A source that has to be made contiguous before the runtime can take it
(a column panel of a C-ordered operand for the stream engine, a chip's
block of one for `parallel/sharding.place`) is packed into a slot of a
small ring of host buffers that are reused from copy to copy and call
to call, never into a fresh array: on the v5e host the first touch of
freshly mapped pages runs at 0.74-0.92 GB/s, the same copy into a
reused, already touched buffer at 10.8-11.2 (PERF.md, PR 26). A slot is
recycled only when the transfer that read it is over (the span
``<owner>::wait_ring`` is the wait), and on a backend whose device
arrays may alias host memory (the CPU) the user of the ring makes the
put copy (`aliases_host`).
"""

from __future__ import annotations

import threading
from typing import Any, Optional, Tuple

import jax
import numpy as np

from ..obs import events as obs_events


class StageSlot:
    """One reused host staging buffer of a ring."""

    __slots__ = ("buf", "last", "busy")

    def __init__(self) -> None:
        self.buf: Optional[np.ndarray] = None   # flat uint8
        self.last: Any = None     # device array last made from buf
        self.busy = False         # a thread is packing into buf


class StageRing:
    """A ring of reused host staging buffers, one per user for the
    life of the process (the stream engine's behind ``_h2d``, the
    mesh's behind ``place``). ``acquire`` hands out a slot whose last
    transfer is over, ``release`` takes it back with the device array
    just made from it. Each slot grows to the largest copy staged
    through it and is kept. The lock covers the bookkeeping only,
    never a copy or a wait for the device. `owner` names the user in
    the wait's span, ``<owner>::wait_ring``."""

    def __init__(self, owner: str, slots: int = 2) -> None:
        self._owner = owner
        self._cv = threading.Condition()
        #: least recently released first: of the free slots the head
        #: is the one whose transfer has had the longest to finish
        self._slots: list = []
        self._cap = int(slots)

    def reserve(self, slots: int) -> None:
        """Allow up to `slots` slots: one being packed per staging
        thread and one per transfer in flight; the most asked for
        wins."""
        with self._cv:
            self._cap = max(self._cap, int(slots))

    def _sweep(self) -> None:
        # under the lock: forget every device array whose transfer is
        # over, so the ring never keeps a consumed panel alive in HBM
        for s in self._slots:
            if s.last is not None and s.last.is_ready():
                s.last = None

    def sweep(self) -> None:
        with self._cv:
            self._sweep()

    def acquire(self, nbytes: int) -> Tuple[StageSlot, bool]:
        """A slot of at least `nbytes` that nothing reads any more,
        and whether its pages were touched before (False for a new or
        regrown buffer). Blocks, under ``<owner>::wait_ring``, while
        every slot is being packed by another thread or while the
        chosen slot's last transfer is not ready: the runtime may read
        a staging buffer until then."""
        with self._cv:
            while True:
                self._sweep()
                free = [s for s in self._slots if not s.busy]
                slot = next((s for s in free if s.last is None), None)
                if slot is None and len(self._slots) < self._cap:
                    slot = StageSlot()
                    self._slots.append(slot)
                if slot is None and free:
                    slot = free[0]
                if slot is not None:
                    slot.busy = True
                    last, slot.last = slot.last, None
                    break
                with obs_events.span("%s::wait_ring" % self._owner,
                                     cat="staging", on="slot"):
                    self._cv.wait()
        try:
            if last is not None:
                with obs_events.span("%s::wait_ring" % self._owner,
                                     cat="staging", on="transfer"):
                    last.block_until_ready()
            reused = slot.buf is not None and slot.buf.nbytes >= nbytes
            if not reused:
                slot.buf = np.empty(nbytes, np.uint8)
        except BaseException:
            self.release(slot, None)    # a failed transfer, no memory
            raise
        return slot, reused

    def release(self, slot: StageSlot, arr) -> None:
        """Hand `slot` back; `arr` is the device array made from it
        (None when the staging failed), whose readiness the next
        ``acquire`` of this slot waits for."""
        with self._cv:
            slot.last = arr
            slot.busy = False
            self._slots.remove(slot)
            self._slots.append(slot)
            self._sweep()
            self._cv.notify()


def aliases_host(device=None) -> bool:
    """Whether a put of a host buffer to `device` (default: where
    ``jnp.asarray`` puts) may hand back a device array that IS that
    buffer (the CPU backend's zero-copy put): a recycled staging slot
    would then rewrite an earlier array, so the ring's user makes the
    backend copy there."""
    dev = device or jax.config.jax_default_device
    platform = getattr(dev, "platform", dev) or jax.default_backend()
    return platform == "cpu"
