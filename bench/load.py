"""The one traffic generator and the two ways of offering it.

A traffic mix is a JSON file under ``bench/traffic/`` (see its keys in
:func:`generate`). Lengths and arrival gaps are stratified in blocks:
every ``block`` consecutive requests hold the same multiset of prompt
lengths, output lengths and gaps, placed in another order by each seed,
with the seed's own token ids. So seeds change which request comes when,
not how much work a window holds.

* ``"loop": "closed"``: ``clients`` callers, each sending its next
  request when the previous one has finished.
* ``"loop": "open"``: requests due at Poisson arrivals of ``rate_per_s``,
  sent on schedule whatever the server's state. Each is timed from when
  it was due.

Requests go through ``FrontDoor.submit``; each token is stamped on the
host clock where the client thread receives it from its stream.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
import threading
import time
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Spec:
    rid: int
    tokens: np.ndarray
    n_new: int
    at: float = 0.0          # open loop: seconds after the start it is due


@dataclasses.dataclass
class Record:
    spec: Spec
    due: float               # host clock when it was due
    sent: float = 0.0        # host clock when submit returned
    times: List[float] = dataclasses.field(default_factory=list)
    status: str = "pending"
    out: List[int] = dataclasses.field(default_factory=list)
    request: object = None   # the program's Request (t_submit, t_admit)

    @property
    def prompt_len(self) -> int:
        return len(self.spec.tokens)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), stream])


def stratified_lengths(d: Dict, n: int) -> np.ndarray:
    """``n`` lognormal lengths at evenly spaced quantiles, clipped to
    ``[min, max]``: ``median * exp(sigma * z_p)``, p = (i + 0.5) / n."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(d["median"] * np.exp(d["sigma"] * z))
    return np.clip(x, d["min"], d["max"]).astype(np.int64)


def _blocks(values: np.ndarray, n: int, rng) -> np.ndarray:
    """``n`` values: ``values`` (one block) repeated, each repeat in its
    own order."""
    k = -(-n // len(values))
    return np.concatenate([rng.permutation(values) for _ in range(k)])[:n]


def generate(mix: Dict, seed: int, vocab: int) -> List[Spec]:
    """The requests of one run. Mix keys: ``loop``, ``requests`` (pool
    size), ``block`` (requests per stratified block), ``prompt`` and
    ``output`` (``median sigma min max``), and for an open loop
    ``rate_per_s``."""
    n, b = int(mix["requests"]), int(mix["block"])
    P = _blocks(stratified_lengths(mix["prompt"], b), n, _rng(seed, 1))
    O = _blocks(stratified_lengths(mix["output"], b), n, _rng(seed, 2))
    toks = _rng(seed, 3).integers(0, vocab, size=int(P.sum()),
                                  dtype=np.int32)
    at = np.zeros(n)
    if mix["loop"] == "open":
        p = (np.arange(b) + 0.5) / b
        gaps = -np.log1p(-p) / float(mix["rate_per_s"])
        at = np.cumsum(_blocks(gaps, n, _rng(seed, 4)))
    specs, off = [], 0
    for i in range(n):
        specs.append(Spec(rid=i, tokens=toks[off:off + P[i]],
                          n_new=int(O[i]), at=float(at[i])))
        off += P[i]
    return specs


def bucket_lengths(mix: Dict, max_len: int) -> List[int]:
    """The power-of-two prefill buckets this mix's prompts can fall in
    (the engine pads a batch of prompts to the bucket of the longest)."""
    def bucket(n):
        b = 2
        while b < n:
            b *= 2
        return min(b, max_len)
    lo, hi = bucket(mix["prompt"]["min"]), bucket(mix["prompt"]["max"])
    out, b = [], lo
    while b <= hi:
        out.append(b)
        b *= 2
    return out


def send(door, spec: Spec, due: float) -> Record:
    """Submit one request and read its stream to the end, stamping each
    token as it arrives."""
    rec = Record(spec=spec, due=due)
    stream = door.submit(spec.tokens, spec.n_new, rid=spec.rid)
    rec.sent = time.perf_counter()
    if stream is None:
        rec.status = "rejected"
        return rec
    rec.request = stream.request
    for _ in stream:
        rec.times.append(time.perf_counter())
    rec.status = stream.status
    rec.out = stream.tokens()
    return rec


class Clients:
    """Offers a mix to a front door from client threads until
    :meth:`stop`; :meth:`join` waits for every request sent to finish."""

    def __init__(self, door, mix: Dict, specs: List[Spec]):
        self.door, self.mix, self.specs = door, mix, specs
        self.records: List[Record] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self.t0 = 0.0

    def _keep(self, rec: Record) -> None:
        with self._lock:
            self.records.append(rec)

    def _client(self, specs: List[Spec]) -> None:
        for spec in specs:
            if self._stop.is_set():
                return
            self._keep(send(self.door, spec, time.perf_counter()))

    def _one(self, spec: Spec, due: float) -> None:
        self._keep(send(self.door, spec, due))

    def _dispatch(self) -> None:
        for spec in self.specs:
            due = self.t0 + spec.at
            while True:
                wait = due - time.perf_counter()
                if self._stop.is_set():
                    return
                if wait <= 0:
                    break
                self._stop.wait(min(wait, 0.05))
            t = threading.Thread(target=self._one, args=(spec, due),
                                 daemon=True)
            with self._lock:
                self._threads.append(t)
            t.start()
        raise RuntimeError("open-loop mix ran out of requests: raise "
                           "'requests' in its file")

    def start(self) -> "Clients":
        self.t0 = time.perf_counter()
        if self.mix["loop"] == "closed":
            k = int(self.mix["clients"])
            for c in range(k):
                t = threading.Thread(target=self._client,
                                     args=(self.specs[c::k],), daemon=True)
                self._threads.append(t)
        elif self.mix["loop"] == "open":
            self._threads.append(threading.Thread(target=self._dispatch,
                                                  daemon=True))
        else:
            raise ValueError(f"unknown loop {self.mix['loop']!r}")
        for t in list(self._threads):
            t.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def join(self, timeout: float) -> bool:
        """Wait for every client thread; True when all have ended."""
        end = time.monotonic() + timeout
        while True:
            with self._lock:
                alive = [t for t in self._threads if t.is_alive()]
            if not alive:
                return True
            left = end - time.monotonic()
            if left <= 0:
                return False
            alive[0].join(min(left, 1.0))

    def lateness_s(self) -> Optional[float]:
        """Open loop: the largest delay from a request's due time to its
        submit returning (the generator's own lateness)."""
        if self.mix["loop"] != "open" or not self.records:
            return None
        return max(r.sent - r.due for r in self.records)


def p95(values: List[float]) -> float:
    """95th percentile by linear interpolation (``numpy.percentile``)."""
    if not values:
        return math.nan
    return float(np.percentile(np.asarray(values, dtype=np.float64), 95))
