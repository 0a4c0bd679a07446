"""Process-wide telemetry of the serving engine (DESIGN.md §15).

One registry per process keeps three things, so that they outlive the
engines that wrote them, as a metrics registry does in a server:

* **Spans** of the engine's host path, one set per tick, in a ring of
  ``RING_ROWS`` preallocated rows: the span's name, its start and end on
  ``time.perf_counter_ns``, the tick id the spans of one tick share, the
  enclosing span, and a count of what the span moved (frames staged,
  bytes uploaded, churn rows applied). When the ring is full the newest
  row overwrites the oldest; :func:`snapshot` says how many were lost.
* **Counters**, monotone int64, each the sum of one span's counts over
  the process's life: ``ticks``, ``frames_fed``, ``h2d_bytes``,
  ``churn_flushes``, ``churn_rows``.
* **Layer scopes** of each compiled step program: HLO instruction name ->
  the innermost ``jax.named_scope`` layer (:data:`LAYERS`) in its
  ``op_name``, ``unscoped`` where it has none (the compiler's own
  instructions take a neighbour's; :meth:`Telemetry.record_scopes`). A
  device trace names its ops after these instructions.

Each span also opens a ``jax.profiler.TraceAnnotation`` of the same name,
so a profile shows it on the device trace's clock. Nothing here waits
for the device or reads a device array. Spans are written from the
thread that drives the engine; they are not safe to write from two
threads at once.
"""

from __future__ import annotations

import re
import time

import numpy as np

import jax

SPANS = ("engine.step", "engine.stage", "engine.churn_flush",
         "engine.upload", "engine.dispatch", "engine.result")
STEP, STAGE, CHURN_FLUSH, UPLOAD, DISPATCH, RESULT = range(len(SPANS))

COUNTERS = ("ticks", "frames_fed", "h2d_bytes", "churn_flushes",
            "churn_rows")
TICKS, FRAMES_FED, H2D_BYTES, CHURN_FLUSHES, CHURN_ROWS = range(len(COUNTERS))
# the counter each span's count adds to (-1: none); every churn flush
# also adds one to ``churn_flushes``
_SUM_INTO = (TICKS, FRAMES_FED, CHURN_ROWS, H2D_BYTES, -1, -1)

LAYERS = ("sensor", "frontend", "embed", "encoder", "policy", "meters")
UNSCOPED = "unscoped"

RING_ROWS = 1 << 17
# ring columns
_NAME, _START, _END, _TICK, _PARENT, _COUNT = range(6)
_COLS = 6

_INSTR = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=(.*)")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)


class _Span:
    """One span name's context manager, reused by every span of that name
    (a name never nests in itself), so opening a span allocates only its
    profiler annotation."""

    __slots__ = ("tel", "name", "tick", "count", "parent", "start", "ann")

    def __init__(self, tel: "Telemetry", name: int):
        self.tel, self.name = tel, name
        self.tick = self.count = self.start = 0
        self.parent = -1
        self.ann = None

    def __enter__(self):
        tel = self.tel
        if tel._open:
            up = tel._open[-1]
            self.parent = up.name
            if self.tick is None:
                self.tick = up.tick
        else:
            self.parent = -1
            if self.tick is None:
                self.tick = tel._next_tick
        tel._open.append(self)
        self.ann = jax.profiler.TraceAnnotation(SPANS[self.name])
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        end = time.perf_counter_ns()
        self.ann.__exit__(None, None, None)
        self.ann = None
        tel = self.tel
        tel._open.pop()
        ok = exc_type is None
        # one flat preallocated list: a slice write is the cheapest row
        # write Python has, and never resizes the list
        i = (tel._n % tel.rows) * _COLS
        tel._ring[i:i + _COLS] = (self.name, self.start, end, self.tick,
                                  self.parent, self.count if ok else 0)
        tel._n += 1
        if ok:
            if _SUM_INTO[self.name] >= 0:
                tel._counts[_SUM_INTO[self.name]] += self.count
            if self.name == CHURN_FLUSH:
                tel._counts[CHURN_FLUSHES] += 1
        return False


class Telemetry:
    """The registry: span ring, counters, layer-scope maps."""

    def __init__(self, rows: int = RING_ROWS):
        self.rows = rows
        self._ring = [0] * (rows * _COLS)
        self._n = 0                      # rows ever written
        self._next_tick = 0
        self._open: list[_Span] = []     # spans open now, innermost last
        self._spans = tuple(_Span(self, i) for i in range(len(SPANS)))
        self._counts = [0] * len(COUNTERS)
        self.scopes: dict[str, dict[str, str]] = {}

    def new_tick(self) -> int:
        """A fresh tick id for the spans of one engine step."""
        t = self._next_tick
        self._next_tick += 1
        return t

    def span(self, name: int, tick: int | None = None, count: int = 0):
        """Context manager timing one span. ``tick=None`` takes the
        enclosing span's tick, or, outside any span, the id the next
        step will take (a churn flush from a state read belongs to the
        tick it precedes). ``count`` is added to the span's counter when
        the span ends without an exception."""
        s = self._spans[name]
        s.tick, s.count = tick, count
        return s

    def record_scopes(self, hlo_text: str) -> dict[str, str]:
        """Parse a compiled program's HLO text into its layer-scope map
        and keep it under the program's name; returns the map.

        An instruction with an ``op_name`` maps to the innermost layer
        in it, or ``unscoped``. One without (what the compiler adds:
        copies, prefetches, in-place update fusions) maps to the layer of
        an instruction it feeds, else of one that feeds it, else
        ``unscoped``."""
        program = _MODULE.search(hlo_text).group(1)
        scopes, unnamed, operands = {}, [], {}
        for line in hlo_text.splitlines():
            m = _INSTR.match(line)
            if not m:
                continue
            name, rest = m.groups()
            operands[name] = _REF.findall(rest)
            op = _OP_NAME.search(rest)
            if op is None:
                unnamed.append(name)
                continue
            parts = op.group(1).split("/")
            scopes[name] = next(
                (p for p in reversed(parts) if p in LAYERS), UNSCOPED)
        users: dict[str, list[str]] = {}
        for name, refs in operands.items():
            for r in refs:
                users.setdefault(r, []).append(name)
        for near in (users, operands):      # consumers first, to a fixpoint
            changed = True
            while changed:
                changed = False
                for name in unnamed:
                    if name in scopes:
                        continue
                    layer = next((scopes[n] for n in near.get(name, ())
                                  if scopes.get(n, UNSCOPED) != UNSCOPED),
                                 None)
                    if layer is not None:
                        scopes[name] = layer
                        changed = True
        for name in unnamed:
            scopes.setdefault(name, UNSCOPED)
        self.scopes[program] = scopes
        return scopes

    def snapshot(self) -> dict:
        """Copies of the ring's rows, oldest first, and of the counters.

        ``spans`` holds one array per column (``name`` as strings,
        ``start_ns``, ``end_ns``, ``tick``, ``parent`` as a span name or
        ``""``, ``count``); ``written`` counts every span ever recorded
        and ``dropped`` those the ring has overwritten."""
        rows, n = self.rows, self._n
        ring = np.asarray(self._ring, np.int64).reshape(rows, _COLS)
        ring = ring[:n] if n <= rows else np.roll(ring, -(n % rows), axis=0)
        names = np.asarray(SPANS)
        parent = np.where(ring[:, _PARENT] >= 0,
                          names[np.maximum(ring[:, _PARENT], 0)], "")
        return {
            "spans": {"name": names[ring[:, _NAME]],
                      "start_ns": ring[:, _START], "end_ns": ring[:, _END],
                      "tick": ring[:, _TICK], "parent": parent,
                      "count": ring[:, _COUNT]},
            "written": n, "dropped": max(0, n - rows),
            "counters": dict(zip(COUNTERS, np.asarray(self._counts,
                                                      np.int64).tolist())),
            "scopes": {k: dict(v) for k, v in self.scopes.items()},
        }


REGISTRY = Telemetry()


def snapshot() -> dict:
    """The process registry's :meth:`Telemetry.snapshot`: the operator's
    read path (DESIGN.md §15)."""
    return REGISTRY.snapshot()
