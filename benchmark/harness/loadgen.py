"""One general load generator, driven by a traffic file.

The *schedule* is a pure function of (traffic parameters, ``--seed``):
the traffic file's ``mix_seed`` fixes one pool of prompt lengths,
output lengths and inter-arrival gaps, and ``--seed`` draws the token
ids and the order in which the pool is offered.  Every seed therefore
offers the same set of sizes and arrivals in another order: a seed
changes which request meets which, and not the amount of work.

The *clients* replay a schedule against anything with the driver's
``submit(prompt, max_new, stream=True)`` / ``iter_tokens()`` surface:

- closed loop: ``callers`` threads, each sends its next request when
  its last one completed (offline and batch callers);
- open loop: requests are sent when they are due whether or not earlier
  ones have finished (independent users).  Latency is measured from the
  due time, and how late the generator ran is reported.

Times are ``time.monotonic()`` seconds; a client stamps tokens when it
receives them, so every latency here is client side.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
import time
from typing import Optional

import numpy as np

FIRST_TOKEN_ID = 3      # ids 0..2 are left to pad/bos/eos conventions


def draw_lengths(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` whole lengths from a length spec: ``{"dist": "lognormal",
    "median", "sigma", "min", "max"}``, ``{"dist": "uniform", "min",
    "max"}`` or ``{"dist": "fixed", "value"}``; clipped to [min, max]."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    lo, hi = int(spec["min"]), int(spec["max"])
    if dist == "uniform":
        return rng.integers(lo, hi + 1, n).astype(np.int64)
    if dist == "lognormal":
        xs = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(xs), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def draw_gaps(arrivals: dict, n: int, rng) -> np.ndarray:
    """``n`` inter-arrival gaps in seconds at mean rate ``rate_per_s``:
    ``poisson`` (exponential gaps) or ``gamma`` with coefficient of
    variation ``cv`` (bursts for cv > 1)."""
    rate = float(arrivals["rate_per_s"])
    kind = arrivals.get("process", "poisson")
    if kind == "poisson":
        return rng.exponential(1.0 / rate, n)
    if kind == "gamma":
        shape = 1.0 / float(arrivals["cv"]) ** 2
        return rng.gamma(shape, 1.0 / (rate * shape), n)
    raise ValueError(f"unknown arrival process {kind!r}")


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request of a schedule.  ``due_s`` is relative to the opening
    of the measured window (negative during the ramp); None in a closed
    loop, where a request is due when a caller is free."""
    index: int
    prompt_len: int
    max_new: int
    due_s: Optional[float]


class Schedule:
    """The requests a run offers, in order.  Indexable without end for
    a closed loop (the pool repeats, with fresh token ids); finite for
    an open loop."""

    def __init__(self, traffic: dict, seed: int, seconds: float,
                 vocab_size: int):
        self.loop = traffic["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"loop must be closed or open: {self.loop!r}")
        self.seed = int(seed)
        self.vocab_size = int(vocab_size)
        self.callers = int(traffic.get("callers", 0))
        self.ramp_s = float(traffic.get("ramp_s", 0.0))
        pool = int(traffic["pool"])
        mix = np.random.default_rng(int(traffic["mix_seed"]))
        prompts = draw_lengths(traffic["prompt_len"], pool, mix)
        outputs = draw_lengths(traffic["output_len"], pool, mix)
        # The seed never changes the pool's contents, only the order in
        # which they are offered (and the token ids).
        order = np.random.default_rng([self.seed, 0]).permutation(pool)
        self._prompts = prompts[order]
        self._outputs = outputs[order]
        self._due = None
        if self.loop == "open":
            gaps = draw_gaps(traffic["arrivals"], pool, mix)
            gap_order = np.random.default_rng(
                [self.seed, 1]).permutation(pool)
            horizon = self.ramp_s + float(seconds)
            reps = 1 + int(horizon * float(
                traffic["arrivals"]["rate_per_s"]) * 2 // pool)
            due = np.cumsum(np.tile(gaps[gap_order], reps)) - self.ramp_s
            self._due = due[due < float(seconds)]
        elif self.callers < 1:
            raise ValueError("a closed loop needs callers >= 1")

    def __len__(self) -> int:
        if self._due is None:
            raise TypeError("a closed-loop schedule has no end")
        return len(self._due)

    def __getitem__(self, i: int) -> Planned:
        if self._due is not None and not 0 <= i < len(self._due):
            raise IndexError(i)
        j = i % len(self._prompts)
        return Planned(i, int(self._prompts[j]), int(self._outputs[j]),
                       None if self._due is None else float(self._due[i]))

    def prompt_tokens(self, i: int) -> list:
        """Token ids of request ``i``: a function of (seed, i) alone."""
        rng = np.random.default_rng([self.seed, 2, i])
        return rng.integers(FIRST_TOKEN_ID, self.vocab_size,
                            self[i].prompt_len).tolist()


@dataclasses.dataclass
class Record:
    """What a client saw of one request."""
    planned: Planned
    prompt: list
    due_at: Optional[float] = None       # monotonic; open loop only
    sent_at: Optional[float] = None
    chunks: list = dataclasses.field(default_factory=list)  # (t, n)
    tokens: list = dataclasses.field(default_factory=list)
    ended_at: Optional[float] = None
    status: str = "pending"     # ok | refused | error | pending
    error: str = ""
    handle: object = None
    abandoned: bool = False     # cut off by the harness after the window

    @property
    def first_token_at(self) -> Optional[float]:
        return self.chunks[0][0] if self.chunks else None

    def token_times(self) -> list:
        """Receive time of every generated token (tokens of one commit
        arrive together and share a time)."""
        return [t for t, n in self.chunks for _ in range(n)]


def _follow(rec: Record, submit, annotate) -> None:
    """Send one request and read its stream to the end (any thread)."""
    rec.sent_at = time.monotonic()
    try:
        with annotate("bench/submit"):
            rec.handle = submit(rec.prompt, rec.planned.max_new)
    except Exception as e:  # noqa: BLE001 — a refusal is a result
        rec.status = "refused"
        rec.error = f"{type(e).__name__}: {e}"
        rec.ended_at = time.monotonic()
        return
    try:
        for toks in rec.handle.iter_tokens():
            now = time.monotonic()
            with annotate("bench/harvest"):
                rec.chunks.append((now, len(toks)))
                rec.tokens.extend(toks)
        rec.status = "ok"
    except Exception as e:  # noqa: BLE001 — a failure is a result
        rec.status = "error"
        rec.error = f"{type(e).__name__}: {e}"
    rec.ended_at = time.monotonic()


def no_annotation(_name: str):
    return contextlib.nullcontext()


class LoadRun:
    """Replays a schedule.  ``start()`` begins the ramp at once; the
    measured window is [``t_open``, ``t_open + seconds``).  ``finish()``
    stops offering, gives in-flight requests ``drain_s`` to end, abandons
    what is left through ``abandon(handle)`` and joins every thread."""

    def __init__(self, schedule: Schedule, submit, abandon, seconds: float,
                 drain_s: float = 0.0, annotate=no_annotation):
        self.schedule = schedule
        self._submit = submit
        self._abandon = abandon
        self.seconds = float(seconds)
        self.drain_s = float(drain_s)
        self._annotate = annotate
        self.records: list = []
        self._lock = threading.Lock()
        self._next = 0
        self._stop = threading.Event()
        self._threads: list = []
        self.t_open: Optional[float] = None
        self.max_in_flight = 0
        self._in_flight = 0

    # -- bookkeeping -----------------------------------------------------

    def _take(self, due_at=None) -> Record:
        with self._lock:
            i = self._next
            self._next += 1
            rec = Record(self.schedule[i], self.schedule.prompt_tokens(i),
                         due_at=due_at)
            self.records.append(rec)
            self._in_flight += 1
            self.max_in_flight = max(self.max_in_flight, self._in_flight)
        return rec

    def _done(self) -> None:
        with self._lock:
            self._in_flight -= 1

    # -- the two loops ---------------------------------------------------

    def _caller(self) -> None:
        while not self._stop.is_set():
            rec = self._take()
            _follow(rec, self._submit, self._annotate)
            self._done()
            if rec.status == "refused":
                # A closed-loop caller that is refused waits a moment
                # instead of spinning on the admission queue.
                self._stop.wait(0.05)

    def _one(self, rec: Record) -> None:
        _follow(rec, self._submit, self._annotate)
        self._done()

    def _arrivals(self) -> None:
        for i in range(len(self.schedule)):
            due_at = self.t_open + self.schedule[i].due_s
            delay = due_at - time.monotonic()
            if delay > 0 and self._stop.wait(delay):
                return
            if self._stop.is_set():
                return
            rec = self._take(due_at)
            t = threading.Thread(target=self._one, args=(rec,),
                                 name=f"bench-req-{i}", daemon=True)
            with self._lock:
                self._threads.append(t)
            t.start()

    # -- lifecycle -------------------------------------------------------

    def start(self) -> float:
        """Begin offering load; returns ``t_open``."""
        self.t_open = time.monotonic() + self.schedule.ramp_s
        if self.schedule.loop == "closed":
            self._threads = [
                threading.Thread(target=self._caller,
                                 name=f"bench-caller-{i}", daemon=True)
                for i in range(self.schedule.callers)]
        else:
            self._threads = [threading.Thread(
                target=self._arrivals, name="bench-arrivals", daemon=True)]
        for t in list(self._threads):
            t.start()
        return self.t_open

    def wait_window(self) -> None:
        delay = self.t_open + self.seconds - time.monotonic()
        if delay > 0:
            time.sleep(delay)

    def finish(self, join_timeout: float = 60.0) -> None:
        self._stop.set()
        deadline = time.monotonic() + self.drain_s
        while time.monotonic() < deadline:
            with self._lock:
                if self._in_flight == 0:
                    break
            time.sleep(0.01)
        with self._lock:
            pending = [r for r in self.records if r.status == "pending"]
        for rec in pending:
            rec.abandoned = True
            if rec.handle is not None:
                self._abandon(rec.handle)
        end = time.monotonic() + join_timeout
        with self._lock:
            threads = list(self._threads)
        for t in threads:
            t.join(max(0.0, end - time.monotonic()))
        alive = [t.name for t in threads if t.is_alive()]
        if alive:
            raise RuntimeError(f"load threads still alive: {alive[:5]}")


# -- reduction of the records to what a user saw ---------------------------

QUIET_S = 0.05      # receive times closer than this are one commit


def commit_clusters(times) -> list:
    """Group token receive times into commits: the engine hands every
    lane its tokens at once, so the clients' stamps of one commit lie
    within milliseconds.  Returns [(first, last, tokens)] in order."""
    out = []
    for t in sorted(times):
        if out and t - out[-1][1] <= QUIET_S:
            out[-1][1] = t
            out[-1][2] += 1
        else:
            out.append([t, t, 1])
    return [tuple(c) for c in out]


def committed_rate(times, t_open: float, t_close: float):
    """Tokens per second between two commits: from the end of the first
    commit that lies wholly inside the window to the end of the last one
    that does, counting every token received in between.  Tokens reach
    clients a chunk at a time, so the window's own quotient gains or
    loses a whole chunk of every lane by where its edges fall; this one
    holds a whole number of chunk periods.  It leaves out the window's
    edges, so it stands beside the end-to-end rate as a steadier
    per-layer reading and never in its place.  Returns (rate, tokens,
    span_s, commits) or None with fewer than two commits."""
    inside = [c for c in commit_clusters(times)
              if c[0] >= t_open and c[1] < t_close]
    if len(inside) < 2:
        return None
    span = inside[-1][1] - inside[0][1]
    tokens = sum(c[2] for c in inside[1:])
    return tokens / span, tokens, span, len(inside)


def window_metrics(records, t_open: float, seconds: float, loop: str) -> dict:
    """Client-side numbers of the measured window.

    - ``tokens``: output tokens clients received inside the window, so
      ``tokens / seconds`` is the rate over all the work and all the
      time of the window; ``committed``: ``committed_rate`` of the same
      tokens;
    - ``gaps_ms``: gaps between consecutive tokens of one request whose
      later token fell inside the window;
    - ``ttft_ms``: first-token time of every request *due* (open loop)
      or sent (closed loop) inside the window, from that due time; a
      request that was refused, failed or never produced a token has
      none and counts as failed;
    - ``late_ms``: how late the generator sent each due request;
    - ``attempted`` / ``failed``: open loop, requests due in the window
      and those refused, failed or unfinished at drain; closed loop,
      requests that ended inside the window and those that ended badly;
    - ``lanes_at_open``: requests that were decoding when it opened.
    """
    t_close = t_open + seconds
    all_times = []
    gaps, ttft, late = [], [], []
    attempted = failed = lanes_at_open = 0
    finished = []
    halves = ([], [])       # ttft of requests due in each half (open)
    for r in records:
        times = r.token_times()
        all_times.extend(times)
        gaps.extend((b - a) * 1e3 for a, b in zip(times, times[1:])
                    if t_open <= b < t_close)
        if times and times[0] < t_open and (
                r.ended_at is None or r.ended_at >= t_open):
            lanes_at_open += 1
        good = (r.status == "ok"
                and len(r.tokens) == r.planned.max_new)
        if loop == "open":
            if not t_open <= r.due_at < t_close:
                continue
            attempted += 1
            late.append((r.sent_at - r.due_at) * 1e3)
            if good:
                ttft.append((r.first_token_at - r.due_at) * 1e3)
                halves[r.due_at >= t_open + seconds / 2].append(ttft[-1])
                finished.append(r)
            else:
                failed += 1
        else:
            if r.first_token_at is not None and \
                    t_open <= r.first_token_at < t_close:
                ttft.append((r.first_token_at - r.sent_at) * 1e3)
            if (r.abandoned or r.ended_at is None
                    or not t_open <= r.ended_at < t_close):
                continue
            attempted += 1
            if good:
                finished.append(r)
            else:
                failed += 1
    timeline = [(round(c[1] - t_open, 3), c[2])
                for c in commit_clusters(all_times)
                if t_open - 5.0 <= c[0] < t_close + 1.0]
    return {"committed": committed_rate(all_times, t_open, t_close),
            "timeline": timeline,
            "tokens": sum(1 for t in all_times if t_open <= t < t_close),
            "gaps_ms": gaps, "ttft_ms": ttft, "late_ms": late,
            "attempted": attempted, "failed": failed,
            "lanes_at_open": lanes_at_open, "finished": finished,
            # A backlog that grows shows as a second half slower than
            # the first: the sign of a rate above what is sustained.
            "ttft_halves_ms": [float(np.median(h)) if h else None
                               for h in halves]}
