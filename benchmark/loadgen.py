"""Load from this process's own threads over real HTTP (the chip belongs
to one process, so the clients live beside the node).

Open loop: one pacing thread hands each call to a small pool of workers
when it is DUE; a call is timed from when it was due to when its body was
read, and (sent - due) is the generator's own lateness.
"""

from __future__ import annotations

import http.client
import queue
import threading
import time
from dataclasses import dataclass
from typing import List, Optional

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
    request: Request


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
            except (OSError, http.client.HTTPException):
                self.close()
                if attempt:
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
            s = Sent(i, due, sent, time.monotonic() - t0, status, raw, req)
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
