"""The one traffic generator: a mix's parameter file in, a seeded schedule
of camera frames out.

Two loops, chosen by the mix's ``loop`` key:

* ``open`` — independent cameras. ``streams`` cameras are live at every
  moment, in equal shares at each of ``rates_hz``, each from a random
  phase, so frame ``n`` of a camera admitted at ``t_a`` is due at
  ``t_a + phase + n / rate`` whatever the server does. A share
  ``moving_share`` of each rate's cameras see a new scene at every frame;
  the others watch one still scene that intruders cross: each intruder
  an ``intruder_px`` square crossing the frame in a straight line over
  ``intruder_s`` seconds. Activity is Zipf-skewed over the still
  cameras: the camera of activity rank ``r`` sees intruders at a rate in
  proportion to ``r ** -activity_zipf_s``, ``intruder_rate_per_s`` per
  camera on average. Churn comes in bursts, ``churn_interval_s`` apart
  on average, at seeded times: each evicts ``churn_share`` of the live
  cameras and admits as many new ones, each taking the place of one it
  replaces (rate, kind, activity rank and the intruders still to come
  there) with a new phase and scene.
* ``closed`` — ``streams`` cameras, every one fed at every tick; camera
  ``s`` sees scene ``(s + n) % scenes`` at its ``n``-th frame, so every
  frame differs from the one before.

Every seed gives the same amount of work in another order: the same
cameras per rate and kind, the same intruder count at each activity rank
(ranks dealt round the rates), the same number of churn bursts and
swaps. The seed places the cameras, phases, scenes, intruder times and
paths, and churn times.

A frame is described by (scene index, intruder box, intruder colour);
the server paints it on the host and the reference on the device.
"""

from __future__ import annotations

import dataclasses
import itertools

import numpy as np

# the keys each loop reads; a mix with any other key is refused
KEYS = {
    "open": {"loop", "streams", "rates_hz", "moving_share", "scenes",
             "intruder_rate_per_s", "activity_zipf_s", "intruder_s",
             "intruder_px", "churn_interval_s", "churn_share", "drain_s",
             "preroll_s", "check_streams", "check_block", "check_t_bucket"},
    "closed": {"loop", "streams", "scenes", "drain_s", "check_streams",
               "check_block", "check_t_bucket"},
}
# keys that describe a mix and change nothing that runs
NOTES = {"source", "status", "knee", "why"}


def check_keys(params: dict) -> None:
    """Refuse a mix whose loop or keys this generator does not implement,
    rather than ignore what it asks for."""
    loop = params.get("loop")
    if loop not in KEYS:
        raise SystemExit(f"chipbench: unknown loop {loop!r}; "
                         f"known: {sorted(KEYS)}")
    extra = sorted(set(params) - KEYS[loop] - NOTES)
    missing = sorted(KEYS[loop] - set(params))
    if extra or missing:
        raise SystemExit(f"chipbench: a {loop}-loop mix with keys the "
                         f"generator does not implement {extra} or without "
                         f"keys it needs {missing}")


@dataclasses.dataclass
class Stream:
    sid: int
    rate: float          # Hz (open loop)
    static: bool
    scene: int           # the still scene, or the moving stream's offset
    t_admit: float
    t_evict: float       # inf while live at the window's end
    phase: float
    events: list         # intruder events: (t0, y0, x0, y1, x1, colour)

    def due(self, n: int) -> float:
        return self.t_admit + self.phase + n / self.rate


@dataclasses.dataclass
class Schedule:
    loop: str
    streams: dict        # sid -> Stream (every stream that ever lives)
    initial: list        # sids admitted before the window opens
    churn: list          # (t, evicted sids, admitted sids), time order
    n_scenes: int
    params: dict

    def frame_spec(self, sid: int, n: int) -> tuple[int, tuple, np.ndarray]:
        """(scene index, box (y0, x0, side), colour) of frame ``n``."""
        st = self.streams[sid]
        p = self.params
        if self.loop == "closed" or not st.static:
            return (st.scene + n) % self.n_scenes, (0, 0, 0), _NO_COLOUR
        t = st.due(n)
        for (t0, y0, x0, y1, x1, colour) in st.events:
            if t0 <= t < t0 + p["intruder_s"]:
                a = (t - t0) / p["intruder_s"]
                return st.scene, (int(round(y0 + a * (y1 - y0))),
                                  int(round(x0 + a * (x1 - x0))),
                                  p["intruder_px"]), colour
        return st.scene, (0, 0, 0), _NO_COLOUR


_NO_COLOUR = np.zeros(3, np.float32)


def zipf_counts(n: int, s: float, total: int) -> list[int]:
    """Intruder counts of activity ranks 1..n: ``total`` dealt in
    proportion to ``r ** -s`` by largest remainders."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -float(s)
    share = w / w.sum() * total
    counts = np.floor(share).astype(int)
    rest = total - int(counts.sum())
    counts[np.argsort(-(share - counts), kind="stable")[:rest]] += 1
    return counts.tolist()


def build(params: dict, seed: int, seconds: float, frame_h: int,
          frame_w: int, streams: int | None = None) -> Schedule:
    """The schedule of one run of ``seconds`` from ``seed``."""
    check_keys(params)
    rng = np.random.default_rng([seed, 0x7AFF1C])
    n = int(streams or params["streams"])
    n_scenes = int(params["scenes"])
    if params["loop"] == "closed":
        sts = {s: Stream(s, 0.0, False, s, 0.0, np.inf, 0.0, [])
               for s in range(n)}
        return Schedule("closed", sts, list(range(n)), [], n_scenes, params)

    # the fleet: (rate, still?) per place, the same for every seed
    rates = [float(r) for r in params["rates_hz"]]
    rate_of = [rates[i % len(rates)] for i in range(n)]
    moving = {}
    for r in rates:
        idx = [i for i in range(n) if rate_of[i] == r]
        moving.update({i: j < round(params["moving_share"] * len(idx))
                       for j, i in enumerate(idx)})
    # activity ranks dealt round the rates: rank 1 to the first still
    # place of the first rate, rank 2 to that of the second rate, ...
    by_rate = [[i for i in range(n) if rate_of[i] == r and not moving[i]]
               for r in rates]
    still = [i for group in itertools.zip_longest(*by_rate) for i in group
             if i is not None]
    px = int(params["intruder_px"])
    total = int(round(params["intruder_rate_per_s"] * len(still) * seconds))
    counts = zipf_counts(len(still), params["activity_zipf_s"], total)
    events = {i: [] for i in range(n)}
    for i, c in zip(still, counts):
        for t in np.sort(rng.uniform(0.0, seconds, size=c)):
            y0, y1 = rng.integers(0, frame_h - px + 1, size=2)
            x0, x1 = rng.integers(0, frame_w - px + 1, size=2)
            colour = rng.uniform(0.7, 1.0, size=3).astype(np.float32)
            events[i].append((float(t), int(y0), int(x0), int(y1), int(x1),
                              colour))

    def new_stream(sid, place, t_admit):
        rate = rate_of[place]
        return Stream(sid, rate, not moving[place],
                      int(rng.integers(n_scenes)), t_admit, np.inf,
                      float(rng.uniform(0.0, 1.0 / rate)),
                      [e for e in events[place] if e[0] >= t_admit])

    # the seed decides which camera id holds which place
    order = rng.permutation(n)
    sts = {}
    place_of = {}
    for sid in range(n):
        sts[sid] = new_stream(sid, int(order[sid]), 0.0)
        place_of[sid] = int(order[sid])
    live = list(range(n))
    initial = list(live)
    n_bursts = max(1, int(round(seconds / params["churn_interval_s"])))
    n_swap = max(1, int(round(params["churn_share"] * n)))
    churn = []
    next_sid = n
    for t in np.sort(rng.uniform(0.0, seconds, size=n_bursts)):
        out = sorted(rng.choice(live, size=n_swap, replace=False).tolist())
        add = []
        for sid in out:
            sts[sid].t_evict = float(t)
            live.remove(sid)
            place = place_of[sid]
            sts[next_sid] = new_stream(next_sid, place, float(t))
            place_of[next_sid] = place
            add.append(next_sid)
            live.append(next_sid)
            next_sid += 1
        churn.append((float(t), out, add))
    return Schedule("open", sts, initial, churn, n_scenes, params)
