"""Start-up's own clock: named phases from the process's start to the
first healthy answer.

This module imports nothing but the standard library, so `cli.py` has
it before the process's first `import jax` (every `cake_tpu.obs.*`
import reaches jax) and that import is a phase like any other. What
reads the clock lives in `cake_tpu/obs/startup.py`: the gauges, the
`startup` block of `/api/v1/health` and the log's `startup:` line.

`phase(name)` reads `time.perf_counter()` at its two ends; it adds no
wait, no sync and no lock to what it times. Phases neither nest nor
overlap, and one name is opened once: a breach raises, by name. What
lies between two phases is `unnamed`: reported, never hidden. The zero
is the process's start as the OS gives it (`/proc/self/stat` field 22
against CLOCK_BOOTTIME; else this module's import), so the
interpreter's start and the imports before `cli.main` are the phase
`boot`, which `start()` files. The clock runs from `start()` (cli.main)
to `healthy()` (the first `/api/v1/health` answered ok); outside that,
`phase()` is a no-op, so a library caller or a test that builds ten
engines in one process files nothing and trips nothing.
"""

from __future__ import annotations

import itertools
import os
import time
from typing import List, Optional, Tuple

_IMPORTED = time.perf_counter()


def process_start(fallback: float) -> Tuple[float, str]:
    """(the process's start on perf_counter's scale, where it came
    from): /proc/self/stat's start time (field 22, clock ticks since
    boot) against CLOCK_BOOTTIME, else `fallback`, a perf_counter
    reading taken as early as the caller could."""
    try:
        with open("/proc/self/stat") as f:
            # the fields after the parenthesised command: field 3 first
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
        zero = time.perf_counter() - age
    except (OSError, ValueError, IndexError, AttributeError):
        return fallback, "import"
    if not zero <= fallback:    # a start after the import: not this clock
        return fallback, "import"
    return zero, "proc"


class _Phase:
    __slots__ = ("_clock", "_name", "_t0")

    def __init__(self, clock: "StartupClock", name: str):
        self._clock = clock
        self._name = name
        self._t0 = None

    def __enter__(self):
        clock = self._clock
        if clock.running:
            clock._admit(self._name)
            clock._open = self._name
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._t0 is None:
            return False
        t1 = time.perf_counter()
        clock = self._clock
        clock._open = None
        if clock.running:    # not closed (healthy) while this was open
            clock._phases.append((self._name, self._t0, t1))
        return False


class StartupClock:
    """Named phases on one clock, in the order they were opened."""

    def __init__(self):
        self.running = False
        self.zero = 0.0
        self.zero_from = "import"
        self.healthy_at: Optional[float] = None
        self._phases: List[Tuple[str, float, float]] = []   # name, t0, t1
        self._open: Optional[str] = None
        self._answers = itertools.count()

    def start(self) -> None:
        """Open the clock (again: what an earlier run filed goes). What
        lies between the process's start and this call is `boot`."""
        now = time.perf_counter()
        self.zero, self.zero_from = process_start(_IMPORTED)
        self.healthy_at = None
        self._phases = [("boot", self.zero, now)]
        self._open = None
        self._answers = itertools.count()
        self.running = True

    def stop(self) -> None:
        """Not a serving process: there is no first healthy answer to
        run to, and nothing filed is read."""
        self.running = False

    def _admit(self, name: str) -> None:
        if self._open is not None:
            raise ValueError(
                f"start-up phase {name!r} opened inside {self._open!r}: "
                "phases do not nest")
        if any(name == p[0] for p in self._phases):
            raise ValueError(f"start-up phase {name!r} opened twice")

    def phase(self, name: str) -> _Phase:
        return _Phase(self, name)

    def healthy(self) -> bool:
        """The first healthy answer: a time, not a phase. Closes the
        clock; True for the one caller whose answer it was."""
        # next() of a count is one step under the interpreter's lock: of
        # two handler threads answering at once, one is given the 0
        if not self.running or next(self._answers):
            return False
        self.healthy_at = time.perf_counter()
        self.running = False
        return True

    @property
    def ran(self) -> bool:
        return self.running or self.healthy_at is not None

    def snapshot(self) -> dict:
        """Phases in order as [name, start_s, seconds] from the zero,
        the mark, and what no phase covered up to the mark (or to now,
        while the clock runs)."""
        end = self.healthy_at
        if end is None:
            end = time.perf_counter()
        phases = [[n, round(t0 - self.zero, 6), round(t1 - t0, 6)]
                  for n, t0, t1 in self._phases]
        span = end - self.zero
        out = {"zero": self.zero_from, "phases": phases,
               "unnamed_s": round(span - sum(p[2] for p in phases), 6)}
        if self.healthy_at is not None:
            out["healthy_s"] = round(span, 6)
        return out


STARTUP = StartupClock()
