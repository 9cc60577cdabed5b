"""The device's own timeline, kept from the syncs the serve loop makes anyway.

One chip runs what it is handed in the order it was handed. The serve loop
blocks on the device at two places (a chunk's tokens, a prefill's first
tokens). When such a sync finds its array NOT ready and waits, the moment it
returns is the moment the device finished that program: an **exact** stamp.
Two exact stamps in a row bracket exactly the programs dispatched between
them, so the interval is device time and is recorded, under the name of the
one heavy program it holds, as ``serve/device_decode_chunk`` or
``serve/device_prefill``. When the array WAS ready the device finished
earlier, nobody knows when: a **late** stamp, counted as
``serve/device_stamp_late``; the interval it would have closed and the one it
would have opened are both given up, and the next exact stamp starts anew.

No sync is added for this and no dispatch moves: the engine calls nothing
here while telemetry is off (``ServingEngine._tl``), and while it is on the
only wait it adds is on the chunk launched ahead of a prefill, which the
prefill's own sync would have waited through anyway.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from ..telemetry import core as telemetry

# the programs an interval is named for; anything else dispatched rides along
# in the interval of the heavy program it ran beside (its ``with``)
HEAVY = ("decode_chunk", "prefill")

# the one refusal: the premise is ONE device queue for everything the loop
# dispatches (tp > 1 is one SPMD queue and is covered)
DISAGGREGATED = "prefill runs on other chips than decode (disaggregated)"

Program = Tuple[str, Dict[str, Any]]


class DeviceTimeline:
    """``refusal``: why the premise (one device queue for every program the
    loop dispatches) does not hold for this engine, or None. A refused
    timeline says so once, by name, and records nothing."""

    def __init__(self, refusal: Optional[str] = None):
        self.refusal = refusal
        self._said = False
        self._last: Optional[float] = None      # the last stamp, if exact
        self._starved_s = 0.0                   # starved seconds since it
        self._open: List[Program] = []          # dispatched, not yet stamped

    def on(self) -> Optional["DeviceTimeline"]:
        """The timeline, for a caller that found telemetry on; None from a
        refused one, which says why the first time it is asked."""
        if self.refusal is None:
            return self
        if not self._said:
            self._said = True
            telemetry.instant("serve/device_timeline_off",
                              reason=self.refusal)
        return None

    def dispatched(self, name: str, **attrs) -> Program:
        """A program was handed to the device just now. Returns the handle
        its sync stamps with."""
        program = (name, attrs)
        self._open.append(program)
        return program

    def _index(self, program: Optional[Program]) -> Optional[int]:
        for i, p in enumerate(self._open):
            if p is program:
                return i
        return None

    def is_open(self, program: Optional[Program]) -> bool:
        """False for a program an earlier stamp already closed (the chunk
        stamped from inside a prefill's wait, consumed a pump later): its
        sync is no stamp at all."""
        return program is not None and self._index(program) is not None

    def stamp(self, program: Optional[Program], span, exact: bool) -> None:
        """The sync on ``program`` returned at the end of the live span
        ``span`` (so that in a profiler's trace the stamp lies beside the
        end of the program it stands for). ``exact``: the array was not
        ready when the sync began."""
        i = self._index(program)
        if i is None or span is telemetry.NOOP_SPAN:
            return      # closed before / telemetry went off under the sync
        t = span.t1
        closed, self._open = self._open[:i + 1], self._open[i + 1:]
        last, starved_s = self._last, self._starved_s
        self._last, self._starved_s = (t if exact else None), 0.0
        if not exact:
            telemetry.count("serve/device_stamp_late")
            return
        heavy = [p for p in closed if p[0] in HEAVY]
        if last is None or len(heavy) != 1:
            return      # no start to measure from / programs never synced
        name, attrs = program
        rode = ",".join(p[0] for p in closed if p[0] not in HEAVY)
        # the chip ran dry between the stamp before and the first dispatch
        # after it: starved time is its own span, not this program's
        telemetry.record_span("serve/device_" + name, last + starved_s, t,
                              **attrs, **{"with": rode})

    def starved(self, seconds: float) -> None:
        """A ``serve/starved_*`` span of that length just closed."""
        self._starved_s += seconds

    def reset(self) -> None:
        """The server ran out of requests: what follows is a chip waiting
        for traffic, and the next exact stamp starts the timeline anew."""
        self._last, self._starved_s = None, 0.0
