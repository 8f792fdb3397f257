"""When the machine ran nothing: a process of its own that asks for two
milliseconds of sleep at a time and writes down every time it got fifty
or more instead.

Why a cell wants to know (PR 38, my chip runs). A one-chip machine
shares its host with others, and whenever a process anywhere on that
host takes or leaves its chip, every process of this machine stands
still for 95-125 ms: the serving loop in whichever part of a step it
was, and a process beside it that touches neither JAX nor the chip, at
the same instant (six runs under such a probe: the two pauses it saw
inside a window were the two steps that ran 99 ms over in that run, and
the five windows in which it saw none had no such step). A run itself
causes three (two while it takes the chip, one as it leaves), all in
set-up. A window of 40 s met none to four of them, a quarter of a
percent of the window each, by the neighbours' luck: that count, and
nothing the program did, was the spread of ``serve_tok_s`` over seeds
(0.74 % over five runs on one machine, 0.24 % over six on another;
with the pauses taken out, reckoned from those runs' spans, 0.2 % on
both).

The probe is a child process, not a thread: a thread of the measured
process would also wait for the interpreter's lock, and would write
down a garbage collection or a long call of the program's own as a
pause of the machine. What the child sees is, by construction, not the
program's doing. It imports nothing of JAX, ends when it is told to,
when its parent is gone, or after :data:`LIFE_S` at the latest, and a cell
that cannot start it, or finds it on another clock, goes without:
:meth:`MachinePauses.stop` then returns ``None`` and nothing is taken
out of the window.

``python -m benchmark.machine_pauses [seconds]`` watches a machine and
prints what it saw.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import List, Optional, Sequence, Tuple

#: What the probe asks for at a time.
PERIOD_S = 0.002
#: The shortest standstill that is written down: ``STALL_MIN_S`` of
#: ``horovod_tpu/serve/metrics.py``, the repo's floor for a stall.
PAUSE_MIN_S = 0.05
#: The probe's and the parent's ``perf_counter`` are held to be one
#: clock if the probe's first stamp reached the parent within this.
SAME_CLOCK_S = 1.0
#: The probe ends by itself after this, whatever became of its parent.
LIFE_S = 3600.0

_PROBE = r"""
import os, sys, time
period, least, life = (float(a) for a in sys.argv[1:4])
parent = int(sys.argv[4])
clock = time.perf_counter
t = born = clock()
print("start %.6f" % t, flush=True)
while os.getppid() == parent and t - born < life:
    time.sleep(period)
    now = clock()
    if now - t >= least:
        print("%.6f %.6f" % (t, now), flush=True)
    t = now
"""


class MachinePauses:
    """The probe, from its start to :meth:`stop`. Use it as a context
    manager around whatever may raise: leaving the block ends the
    child."""

    def __init__(self, parent: Optional[int] = None):
        self.state = "no probe"
        self._proc = None
        try:
            self._proc = subprocess.Popen(
                [sys.executable, "-c", _PROBE, str(PERIOD_S),
                 str(PAUSE_MIN_S), str(LIFE_S),
                 str(os.getpid() if parent is None else parent)],
                stdin=subprocess.DEVNULL, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL, text=True)
            first = self._proc.stdout.readline().split()
            now = time.perf_counter()
            if len(first) == 2 and first[0] == "start":
                near = 0.0 <= now - float(first[1]) <= SAME_CLOCK_S
                self.state = "ok" if near else "another clock"
        except (OSError, ValueError):
            self._end()

    def _end(self) -> str:
        proc, self._proc = self._proc, None
        if proc is None:
            return ""
        proc.terminate()
        try:
            return proc.communicate(timeout=10)[0] or ""
        except (subprocess.TimeoutExpired, OSError, ValueError):
            proc.kill()
            proc.wait()
            return ""

    def stop(self) -> Optional[List[Tuple[float, float]]]:
        """End the probe. The standstills it saw, each from the stamp
        before it to the stamp after it on ``time.perf_counter``, or
        ``None`` where there was no probe to ask."""
        said = self._end()
        if self.state != "ok":
            return None
        pauses = []
        for line in said.splitlines():
            parts = line.split()
            if len(parts) == 2:
                try:
                    pauses.append((float(parts[0]), float(parts[1])))
                except ValueError:      # a line cut short by the end
                    pass
        return pauses

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._end()


def inside(pauses: Optional[Sequence[Tuple[float, float]]], t_open: float,
           t_close: float, stamps: Sequence[float] = ()
           ) -> List[Tuple[float, float]]:
    """Of ``pauses``, those that lie inside the window and in which the
    measured loop finished no step (``stamps``: the host time after
    each of its steps; a loop that got somewhere was not standing
    still; the sleep the probe asked for lies at either end of what it
    saw, so a step may end within two periods of an end), as ``(start,
    seconds stood still)``: the time between the probe's two stamps
    less the :data:`PERIOD_S` it asked for, cut to the window."""
    out = []
    edge = 2 * PERIOD_S
    for a, b in pauses or ():
        lo, hi = max(a, t_open), min(b, t_close)
        if hi - lo <= PERIOD_S or any(a + edge < s < b - edge
                                      for s in stamps):
            continue
        out.append((lo, hi - lo - PERIOD_S))
    return out


if __name__ == "__main__":
    watch = float(sys.argv[1]) if len(sys.argv) > 1 else 10.0
    with MachinePauses() as probe:
        began = time.perf_counter()
        time.sleep(watch)
        state, seen = probe.state, probe.stop()
    print({"probe": state, "watched_s": watch, "pauses": None if seen is None
           else [(round(a - began, 3), round(1e3 * (b - a), 1))
                 for a, b in seen]})
