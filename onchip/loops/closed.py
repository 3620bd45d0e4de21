"""A closed loop: clients that send the next request only as replies
come back, so the queue never grows past what they keep in flight.

Traffic parameters read here:

* ``outstanding``: requests kept in flight.  A multiple of ``batch``
  makes every step find full micro-batches.
"""

from __future__ import annotations

import time
from collections import deque

WARMUP_PASSES = 3        # passes before the window opens


def warm(server, requests, traffic) -> None:
    """Run the served shapes: a few full passes, each drained."""
    for _ in range(WARMUP_PASSES):
        for tenant, _, x in _take(requests, int(traffic["outstanding"])):
            server.submit(x, tenant=tenant)
        server.step()
        server.drain()


def _take(requests, n):
    return [next(requests) for _ in range(n)]


def drive(server, requests, traffic, *, seconds: float, spans, rng=None) -> dict:
    """Keep ``outstanding`` requests in flight for `seconds`.

    Each pass tops the backlog up, calls ``server.step()`` and collects
    what completed, oldest first.  A request's latency runs from just
    before its ``submit`` to the collect that finds it done, with its
    scores on the host.  What is still in flight when the window closes
    is finished, and returned as late: it is checked, but not counted as
    completed in the window."""
    outstanding = int(traffic["outstanding"])
    inflight: deque = deque()
    done, shed = [], []
    with spans("window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with spans("generate"):
                todo = _take(requests, outstanding - len(inflight))
            with spans("submit"):
                for tenant, i, x in todo:
                    t_send = time.perf_counter()
                    req = server.submit(x, tenant=tenant)
                    if req is None:          # refused by admission
                        shed.append((tenant, i))
                    else:
                        inflight.append((tenant, i, t_send, req))
            with spans("step"):
                server.step()
            with spans("collect"):
                now = time.perf_counter()
                while inflight and inflight[0][3].done_t is not None:
                    tenant, i, t_send, req = inflight.popleft()
                    done.append((tenant, i, req.result, now - t_send))
        t1 = time.perf_counter()
    late = []
    while inflight:
        tenant, i, _, req = inflight.popleft()
        with spans("wait"):
            server.drain()
            try:
                late.append((tenant, i, req.wait(timeout=60.0)))
            except Exception:   # noqa: BLE001 -- counted as failed
                late.append((tenant, i, None))
    return {"window_s": t1 - t0, "done": done, "late": late, "shed": shed}
