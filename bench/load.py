"""The load generators, by the `kind` of a traffic file, driven by its
parameters and the seed. There is one: a closed loop.

Each request carries the pool index of its image, so that its answer can
be checked against the reference of that image, and is timed from when it
was due to its answer.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass

import numpy as np

#: seconds to wait for an answer once the window has closed
ANSWER_WAIT_S = 60.0
#: the server answers the requests of one batch within this many seconds
#: of the first, so a window edge this long after an answer lies between
#: batches
BATCH_EDGE_S = 1e-3


@dataclass
class Request:
    image: int                   # index into the input pool
    due: float                   # perf_counter time it was due
    submitted: float = 0.0
    ticket: object = None        # the server's Ticket; None if refused
    refused: bool = False


@dataclass
class Window:
    requests: list               # every request, warm-up included
    t0: float                    # perf_counter start of the window
    t1: float                    # perf_counter end of the window

    def due_in_window(self) -> list:
        return [r for r in self.requests if self.t0 <= r.due < self.t1]


class Submitter:
    """Submits pool images to the server and records each request."""

    def __init__(self, server, pool: np.ndarray, order: np.ndarray,
                 refused_error: type, annotate):
        self.server, self.pool, self.order = server, pool, order
        self.refused_error, self.annotate = refused_error, annotate
        self.requests: list[Request] = []

    def __call__(self, due: float) -> Request:
        r = Request(int(self.order[len(self.requests) % len(self.order)]),
                    due)
        with self.annotate("bench.submit"):
            r.submitted = time.perf_counter()
            try:
                r.ticket = self.server.submit(self.pool[r.image])
            except self.refused_error:
                r.refused = True
        self.requests.append(r)
        return r


def wait(r: Request, timeout: float) -> None:
    """Block until the request has its answer (or error), or `timeout`.
    The outcome is read from the ticket afterwards."""
    if r.ticket is None:
        return
    try:
        r.ticket.result(max(timeout, 0.0))
    except Exception:  # noqa: BLE001 - a failed answer is counted later
        pass


def closed(submit: Submitter, traffic: dict, seconds: float, rng,
           annotate, marks) -> Window:
    """`in_flight` clients, each sending its next request once its last
    one is answered. The first `warm_requests` answers are set-up. The
    window opens just after the batch that answers the last of them and
    closes just after the first batch answered `seconds` later, so that it
    holds whole batches and no part of one. `marks.open()` and
    `marks.close()` are called as it opens and closes."""
    q = deque(submit(time.perf_counter())
              for _ in range(traffic["in_flight"]))
    answered, t0 = 0, None
    while True:
        r = q.popleft()
        with annotate("bench.wait"):
            wait(r, ANSWER_WAIT_S)
        answered += 1
        t = (r.ticket.finished_at if r.ticket is not None and r.ticket.done()
             else time.perf_counter()) + BATCH_EDGE_S
        if t0 is None and answered >= traffic["warm_requests"]:
            t0 = t
            marks.open()
        elif t0 is not None and t >= t0 + seconds:
            t1 = t
            marks.close()
            break
        q.append(submit(time.perf_counter()))
    for r in q:
        wait(r, t1 + ANSWER_WAIT_S - time.perf_counter())
    return Window(submit.requests, t0, t1)


GENERATORS = {"closed": closed}
