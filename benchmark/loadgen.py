"""Load from this process's own threads over real HTTP (the chip belongs
to one process, so the clients live beside the node).

Open loop: one pacing thread hands each call to a small pool of workers
when it is DUE; a call is timed from when it was due to when its body was
read, and (sent - due) is the generator's own lateness.

Closed loop: `clients` callers, each posting its next `_msearch` when the
last is answered. A call is one sample; it is due, and sent, the moment
its client was free.
"""

from __future__ import annotations

import http.client
import itertools
import queue
import threading
import time
from dataclasses import dataclass
from typing import List, Optional

from benchmark import compare
from benchmark.traffic import Mix, Request, Schedule

CLIENT_TIMEOUT_S = 60.0


@dataclass
class Sent:
    """One call as the client saw it."""
    i: int
    due: float
    sent: float
    done: float
    status: int                 # 0 = no answer (time-out, reset)
    raw: bytes
    requests: List[Request]     # one for a _search, `batch` for an _msearch

    def responses(self) -> Optional[list]:
        """The call's search responses, one per request, or None where
        it was not answered: anything but a 200 whose body holds a
        well-formed response to EVERY request."""
        if self.status != 200:
            return None
        return compare.well_formed(self.raw, len(self.requests))


class Conn:
    def __init__(self, port: int, timeout: float = CLIENT_TIMEOUT_S):
        self.port = port
        self.timeout = timeout
        self.c: Optional[http.client.HTTPConnection] = None

    def post(self, path: str, data: bytes, ndjson: bool = False):
        for attempt in (0, 1):
            if self.c is None:
                self.c = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=self.timeout)
            try:
                self.c.request("POST", path, body=data, headers={
                    "Content-Type": "application/x-ndjson" if ndjson
                    else "application/json"})
                resp = self.c.getresponse()
                return resp.status, resp.read()
            except (OSError, http.client.HTTPException) as e:
                self.close()
                # a stale keep-alive connection is worth one more try; a
                # call that timed out is still running on the node, and a
                # second one beside it would be load the mix never asked for
                if attempt or isinstance(e, TimeoutError):
                    return 0, b""
        return 0, b""

    def close(self):
        if self.c is not None:
            self.c.close()
            self.c = None


def open_loop(port: int, mix: Mix, sched: Schedule) -> List[Sent]:
    """Every call of the schedule is sent, however late the system runs;
    returns them in the schedule's order once the last is answered."""
    work: "queue.Queue" = queue.Queue()
    out: List[Sent] = []
    lock = threading.Lock()

    def worker():
        conn = Conn(port)
        while True:
            item = work.get()
            if item is None:
                break
            i, due = item
            path, data, req = mix.call(int(sched.index[i]))
            sent = time.monotonic() - t0
            status, raw = conn.post(path, data)
            s = Sent(i, due, sent, time.monotonic() - t0, status, raw, [req])
            with lock:
                out.append(s)
        conn.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(mix.t.get("connections", 8)))]
    for t in threads:
        t.start()
    t0 = time.monotonic()
    for i, d in enumerate(sched.due):
        wait = d - (time.monotonic() - t0)
        if wait > 0:
            time.sleep(wait)
        work.put((i, float(d)))
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join()
    out.sort(key=lambda s: s.i)
    return out


def closed_loop(port: int, mix: Mix, seconds: float,
                first: int = 0) -> List[Sent]:
    """Each of the mix's `clients` sends call after call (`Mix.closed_call`,
    numbered as the clients take them), none started after `seconds`;
    returns the calls in that order once the last is answered."""
    out: List[Sent] = []
    errs: List[BaseException] = []
    lock = threading.Lock()
    numbers = itertools.count()

    def client():
        conn = Conn(port, float(mix.t.get("timeout_s", CLIENT_TIMEOUT_S)))
        try:
            while time.monotonic() - t0 < seconds:
                i = next(numbers)
                path, data, reqs = mix.msearch(mix.closed_call(i, first))
                sent = time.monotonic() - t0
                status, raw = conn.post(path, data, ndjson=True)
                s = Sent(i, sent, sent, time.monotonic() - t0, status, raw,
                         reqs)
                with lock:
                    out.append(s)
        except BaseException as e:   # noqa: BLE001 — re-raised below
            errs.append(e)
        finally:
            conn.close()

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(int(mix.t["clients"]))]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errs:
        raise errs[0]
    out.sort(key=lambda s: s.i)
    return out
