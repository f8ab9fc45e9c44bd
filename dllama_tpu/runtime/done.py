"""When each program left the device: completion stamps, no profiler.

A program call returns at the enqueue, and the engine learns that the device
has finished one only where it reads the output back, which the lane path
does for some programs and late (a block dispatched ahead is read a block
later, a chunk never). So every enqueued program leaves a small output that
no later program consumes (its *handle*) with a `DoneWatcher`: one daemon
thread that waits for the handles in the order they were enqueued
(`block_until_ready` releases the interpreter's lock) and reads the clock as
each returns. The thread that dispatches keeps the `Program` records and
makes of them what it wants where it next comes by: the watcher touches a
record's `watched`, `error` and `handle` and nothing else, so the two share
no lock.

The watcher's reading can be late by the interpreter's lock, never early.
Where the dispatching thread itself waited for a program (a read-back), its
own reading bounds that program's end and every earlier one's: `read`.
`Program.done` is the earlier of the two.
"""

from __future__ import annotations

import queue
import threading
from typing import Callable


class Program:
    """One enqueued program between its dispatch and its stamp."""

    __slots__ = ("seq", "step", "thread", "t0", "t1", "dry", "handle",
                 "watched", "read", "error")

    def __init__(self, seq: int, step: str, t0: float, t1: float, dry: bool, handle):
        self.seq = seq  # the engine's count of enqueues at its own
        self.step = step
        self.thread = threading.get_ident()  # the thread that dispatched it
        self.t0 = t0  # its dispatch's begin
        self.t1 = t1  # its call's return: the program is enqueued
        self.dry = dry  # the program before it had left the device at `t0`
        self.handle = handle  # dropped by the watcher once it is stamped
        self.watched: float | None = None  # the watcher's reading
        self.read: float | None = None  # the dispatching thread's own, where it waited
        self.error: str | None = None  # the handle raised: the exception's type

    @property
    def done(self) -> float | None:
        """The earliest reading that says the program has left the device;
        None while nobody has seen it finished."""
        known = [t for t in (self.watched, self.read) if t is not None]
        return min(known) if known else None

    def left_the_device(self) -> bool:
        """Without a wait: whether the program has finished. Exact where it
        says no."""
        if self.watched is not None or self.read is not None:
            return True
        handle = self.handle  # the watcher may drop it meanwhile
        if handle is None:
            return True
        try:
            return bool(handle.is_ready())
        except Exception:  # dlint: disable=silent-except — a poisoned or deleted output has left the device too; the watcher stamps its `error`
            return True


def _watch(programs: queue.SimpleQueue, stopped: threading.Event,
           clock: Callable[[], float]) -> None:
    """The watcher thread: stamp each program as its handle becomes ready;
    a handle that raises is stamped too, as `error`. Holds no handle while
    it waits for the next program, and none once `stopped`."""
    while True:
        program = programs.get()
        if program is None:
            return
        if stopped.is_set():
            program.handle = None
            continue
        handle = program.handle
        try:
            handle.block_until_ready()
        except Exception as e:  # dlint: disable=silent-except — the device's fault or a deleted buffer: stamped, and `device_done` says `error`
            program.error = type(e).__name__
        del handle
        program.handle = None
        program.watched = clock()


class DoneWatcher:
    """The thread behind `Program.watched`, started with the first program
    and again after a `close`."""

    def __init__(self, clock: Callable[[], float]):
        self._clock = clock
        self._programs: queue.SimpleQueue = queue.SimpleQueue()
        self._stopped = threading.Event()
        self._thread: threading.Thread | None = None

    def watch(self, program: Program) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._programs, self._stopped = queue.SimpleQueue(), threading.Event()
            self._thread = threading.Thread(
                target=_watch, daemon=True, name="dllama-device-done",
                args=(self._programs, self._stopped, self._clock),
            )
            self._thread.start()
        self._programs.put(program)

    def close(self, timeout: float = 5.0) -> None:
        """End the thread: what it has not begun to wait for is dropped."""
        thread, self._thread = self._thread, None
        if thread is None:
            return
        self._stopped.set()
        self._programs.put(None)
        thread.join(timeout=timeout)

    @property
    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()
