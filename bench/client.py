"""Open-loop client: sends each request at its due time whatever the state
of earlier ones, and watches what comes back.

One thread does both.  It sleeps on the result store's signal
(``wait_store``), never on a fixed poll interval, until the next request is
due or something is stored; then it submits what is due and reads every
outstanding request's streamed prefix (``poll_partial``) and result
(``poll_result``).  All times are seconds from the start of the window on
``time.perf_counter``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Record:
    index: int
    due_s: float
    prompt: np.ndarray              # [1, P] int32
    output_len: int
    uid: Optional[str] = None
    submit_s: Optional[float] = None
    first_s: Optional[float] = None   # first time any output token was seen
    last_s: Optional[float] = None    # time the result was seen
    tokens: Optional[np.ndarray] = None   # [P + n] ids of the result
    error: Optional[str] = None
    seen: List[tuple] = dataclasses.field(default_factory=list)  # (t, n)

    @property
    def finished(self) -> bool:
        return self.tokens is not None and self.error is None

    def ttft_s(self) -> Optional[float]:
        return None if not self.finished else self.first_s - self.due_s

    def tpot_s(self) -> Optional[float]:
        if not self.finished:
            return None
        n = self.tokens.shape[1] - self.prompt.shape[1]
        return (self.last_s - self.first_s) / max(n - 1, 1)

    def tokens_seen_by(self, t: float) -> int:
        n = 0
        for ts, k in self.seen:
            if ts <= t:
                n = k
        return n

    def note(self, t: float, n_out: int) -> None:
        if n_out > (self.seen[-1][1] if self.seen else 0):
            self.seen.append((t, n_out))
            if self.first_s is None:
                self.first_s = t


class OpenLoopClient:
    def __init__(self, proxy, database, app_id: int,
                 payload: Callable[[Record], Dict[str, Any]],
                 dead: Callable[[], set]):
        self.proxy = proxy
        self.database = database
        self.app_id = app_id
        self.payload = payload
        self.dead = dead

    def run(self, records: List[Record], *, window_s: float,
            drain_s: float, t0: float,
            at: Optional[List[tuple]] = None) -> None:
        """Send ``records`` (sorted by due time) from ``t0`` on and watch
        them until all are done or ``window_s + drain_s`` has passed.
        ``at`` holds ``(offset_s, fn)`` hooks to call once each, from this
        thread, when their offset passes."""
        from repro.cluster.proxy import Rejected

        hooks = sorted(at or [], key=lambda h: h[0])
        clock = time.perf_counter
        nxt = 0
        open_: Dict[str, Record] = {}
        deadline = window_s + drain_s
        while True:
            now = clock() - t0
            while hooks and hooks[0][0] <= now:
                hooks.pop(0)[1]()
            while nxt < len(records) and records[nxt].due_s <= now:
                r = records[nxt]
                nxt += 1
                r.submit_s = clock() - t0
                try:
                    r.uid = self.proxy.submit(self.app_id, self.payload(r))
                except Rejected as e:
                    r.error = f"rejected: {e}"
                    continue
                open_[r.uid] = r
            if open_:
                self._poll(open_, t0)
            now = clock() - t0
            if nxt == len(records) and not open_:
                return
            if now >= deadline:
                for r in open_.values():
                    r.error = f"unfinished {deadline:.0f}s after the start"
                return
            wake = deadline
            if nxt < len(records):
                wake = min(wake, records[nxt].due_s)
            if hooks:
                wake = min(wake, hooks[0][0])
            if wake > now:
                self.database.wait_store(min(wake - now, 0.05))

    def _poll(self, open_: Dict[str, Record], t0: float) -> None:
        dead = self.dead()
        for uid, r in list(open_.items()):
            p = r.prompt.shape[1]
            res = self.proxy.poll_result(uid)
            t = time.perf_counter() - t0
            if res is not None:
                res = np.asarray(res)
                r.tokens, r.last_s = res, t
                r.note(t, res.shape[1] - p)
                del open_[uid]
                continue
            if uid in dead:
                r.error = "dropped by the set"
                del open_[uid]
                continue
            part = self.proxy.poll_partial(uid)
            if part is not None:
                r.note(t, int(np.asarray(part).shape[1]) - p)
