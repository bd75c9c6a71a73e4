"""The measured windows: how requests are sent and timed.

Each loop calls ``window.open()`` just before it takes the window's
start time and ``window.close()`` as the window ends; the harness starts
and stops the profiler there.  Answers that are still due when the
window closes are waited for afterwards (up to ``GRACE_S``), timed, and
handed to the check with the rest.

The serving loops keep no future once it has resolved: a done-callback
files its time and result, so the client's heap stays the size of the
requests in flight and its garbage collection does not grow with the
window.
"""

from __future__ import annotations

import queue
import time

import numpy as np

__all__ = ["solve_loop", "open_loop", "closed_loop", "GRACE_S"]

GRACE_S = 60.0
now = time.perf_counter


def solve_loop(system, stream, seconds: float, window) -> dict:
    """Back-to-back blocking solves until ``seconds`` have passed; the
    solve under way when they have is finished and counted."""
    mats, vals = [], []
    window.open()
    t0 = now()
    while True:
        A = stream.next()
        vals.append(system.solve(A))
        mats.append(A)
        if now() - t0 >= seconds:
            break
    t1 = now()
    window.close()
    return {"t0": t0, "t1": t1, "mats": mats, "vals": vals,
            "failed": 0}


class _Ledger:
    """Completion time and answer of each request, by index; a request
    that failed has a time and no answer.  Dicts, because the completer
    thread files into them while the client sends."""

    def __init__(self):
        self.done: dict[int, float] = {}
        self.results: dict = {}

    def callback(self, base: int, notify=None):
        """A done-callback filing a future of ``seq`` as index seq − base."""
        def filed(fut):
            k = fut.seq - base
            if fut.exception() is None:
                self.results[k] = fut.result()
            self.done[k] = now()
            if notify is not None:
                notify(k)
        return filed

    def wait(self, n: int, deadline: float) -> None:
        """Until ``n`` requests have resolved, or ``deadline``."""
        while len(self.done) < n and now() < deadline:
            time.sleep(0.005)

    def record(self, n: int) -> dict:
        results = [self.results.get(k) for k in range(n)]
        return {"done": np.array([self.done.get(k, np.nan)
                                  for k in range(n)]),
                "results": results,
                "failed": int(sum(r is None for r in results))}


def open_loop(system, sched, seconds: float, window) -> dict:
    """Open loop: each request is sent when it falls due, whatever the
    server's progress; requests due together go as one ``submit_many``.
    A request is timed from its due time to its future resolving."""
    n = len(sched)
    ledger = _Ledger()
    sent = np.full(n, np.nan)
    window.open()
    t0 = now()
    due_abs = t0 + sched.due
    i = 0
    while i < n:
        t = now()
        if due_abs[i] > t:
            time.sleep(due_abs[i] - t)
            t = now()
        j = max(int(np.searchsorted(due_abs, t, side="right")), i + 1)
        batch = system.submit_many(sched.mats[i:j], sched.grads[i:j])
        sent[i:j] = now()
        filed = ledger.callback(batch[0].seq - i)
        for f in batch:
            f.add_done_callback(filed)
        i = j
    t_last = now()
    if t0 + seconds > t_last:
        time.sleep(t0 + seconds - t_last)
    t1 = now()
    window.close()
    ledger.wait(n, t1 + GRACE_S)
    return {"t0": t0, "t1": t1, "due": due_abs, "sent": sent,
            **ledger.record(n)}


def closed_loop(system, stream, in_flight: int, seconds: float,
                window) -> dict:
    """Closed loop: ``in_flight`` requests outstanding; each completion
    sends the next request (completions that land together are replaced
    by one ``submit_many``).  Requests that complete inside the window
    count."""
    stream.prepare(in_flight * 8)
    ledger = _Ledger()
    done_q: queue.SimpleQueue = queue.SimpleQueue()
    sent = 0

    def send(count: int) -> None:
        nonlocal sent
        mats, grads = stream.take(sent, count)
        batch = system.submit_many(mats, grads)
        filed = ledger.callback(batch[0].seq - sent, done_q.put)
        sent += count
        for f in batch:
            f.add_done_callback(filed)

    window.open()
    t0 = now()
    send(in_flight)
    while True:
        left = t0 + seconds - now()
        if left <= 0:
            break
        try:
            done_q.get(timeout=left)
        except queue.Empty:
            break
        k = 1
        while True:
            try:
                done_q.get_nowait()
                k += 1
            except queue.Empty:
                break
        if now() < t0 + seconds:
            send(k)
    t1 = now()
    window.close()
    ledger.wait(sent, t1 + GRACE_S)
    return {"t0": t0, "t1": t1, "stream": stream, **ledger.record(sent)}
