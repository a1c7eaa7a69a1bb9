"""The load generator: one process, a thread per request in flight, the
host's monotonic clock.

Open loop: requests leave on the schedule whether or not earlier ones have
finished, and each is timed from when it was DUE, so a stall charges the
requests behind it; how late each really left is kept (a starved generator
must not read as a fast server).  Closed loop: as many clients as the mix
says, each sending its next request when its last completed.

Stdlib only.
"""

from __future__ import annotations

import dataclasses
import http.client
import itertools
import json
import socket
import threading
import time


@dataclasses.dataclass
class Result:
    index: int
    in_window: bool = True     # due (open) or sent (closed) inside the window
    due: float = 0.0           # monotonic
    sent: float = 0.0
    status: int = 0
    error: str | None = None
    cut: bool = False          # closed loop: the window ended under it
    served_by: str | None = None
    t_first: float | None = None   # first chunk that carried text
    t_last: float | None = None    # last chunk that carried text
    tokens: int = 0            # completion tokens (usage, else counted)
    want_tokens: int = 0
    prompt_tokens: int = 0
    adapter: int | None = None
    chunks: list = dataclasses.field(default_factory=list)  # (t, n_tokens)

    @property
    def ok(self) -> bool:
        return self.error is None and self.status == 200 and not self.cut

    @property
    def late_s(self) -> float:
        return self.sent - self.due

    @property
    def ttft_s(self) -> float | None:
        return None if self.t_first is None else self.t_first - self.due

    @property
    def tpot_s(self) -> float | None:
        if self.t_first is None or self.t_last is None or self.tokens < 2:
            return None
        return (self.t_last - self.t_first) / (self.tokens - 1)


def send(host: str, port: int, body: dict, res: Result,
         deadline: float) -> Result:
    """POST one completion and read it to the end (or to ``deadline``, a
    monotonic time).  Streams are read chunk by chunk and each chunk's
    arrival is stamped; with the benchmark's logit_bias one character is one
    token, so a chunk's text length is its token count."""
    res.sent = time.monotonic()
    conn = http.client.HTTPConnection(
        host, port, timeout=max(0.5, deadline - res.sent))
    try:
        conn.request("POST", "/v1/completions", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        res.status = resp.status
        res.served_by = resp.getheader("x-served-by")
        if resp.status != 200:
            res.error = f"HTTP {resp.status}: {resp.read(300)!r}"
            return res
        if not body.get("stream"):
            doc = json.loads(resp.read())
            now = time.monotonic()
            res.t_first = res.t_last = now
            res.tokens = doc["usage"]["completion_tokens"]
            res.chunks.append((now, res.tokens))
            return res
        counted, done = 0, False
        for raw in resp:
            if not raw.startswith(b"data:"):
                continue
            now = time.monotonic()
            data = raw[5:].strip()
            if data == b"[DONE]":
                done = True
                break
            doc = json.loads(data)
            if "error" in doc:
                res.error = f"stream error: {doc['error']}"
                return res
            text = doc["choices"][0].get("text", "")
            if text:
                if res.t_first is None:
                    res.t_first = now
                res.t_last = now
                counted += len(text)
                res.chunks.append((now, len(text)))
            if "usage" in doc:
                res.tokens = doc["usage"]["completion_tokens"]
            if now > deadline:
                break
        if not done:
            res.cut = time.monotonic() >= deadline
            if not res.cut:
                res.error = "stream ended without [DONE]"
        res.tokens = res.tokens or counted
        return res
    except (socket.timeout, TimeoutError):
        if time.monotonic() >= deadline - 0.01:
            res.cut = True
        else:
            res.error = "timed out"
        return res
    except (OSError, http.client.HTTPException, ValueError, KeyError) as e:
        res.error = f"{type(e).__name__}: {e}"
        return res
    finally:
        conn.close()


def run_open(requests, bodies, host: str, port: int, t0: float,
             seconds: float, drain_s: float) -> list[Result]:
    """Offer ``requests`` on their schedule (``due_s`` against ``t0``).  A
    request still unanswered ``drain_s`` after the window has failed."""
    deadline = t0 + seconds + drain_s
    results = [Result(index=r.index, in_window=0.0 <= r.due_s < seconds,
                      due=t0 + r.due_s, want_tokens=r.max_tokens,
                      prompt_tokens=r.prompt_tokens, adapter=r.adapter)
               for r in requests]
    threads = []
    for r, body, res in zip(requests, bodies, results):
        delay = res.due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=send, daemon=True,
                              args=(host, port, body, res, deadline))
        th.start()
        threads.append(th)
    for th, res in zip(threads, results):
        th.join(timeout=max(0.0, deadline - time.monotonic()) + 2.0)
        if th.is_alive() or res.cut:
            res.cut, res.error = False, res.error or "no answer by the drain limit"
    return results


def run_closed(requests, bodies, host: str, port: int, t0: float,
               seconds: float, clients: int) -> list[Result]:
    """``clients`` closed-loop clients taking requests in order, started at
    once (the caller starts this ``ramp_s`` before ``t0``); a request the
    window's end finds in flight is cut, not failed."""
    t_end = t0 + seconds
    counter = itertools.count()
    results: list[Result] = []
    lock = threading.Lock()

    def client() -> None:
        while True:
            i = next(counter)
            now = time.monotonic()
            if i >= len(requests) or now >= t_end:
                return
            r = requests[i]
            res = Result(index=r.index, in_window=now >= t0, due=now,
                         want_tokens=r.max_tokens,
                         prompt_tokens=r.prompt_tokens, adapter=r.adapter)
            with lock:
                results.append(res)
            send(host, port, bodies[i], res, t_end)
            if res.error is not None:
                time.sleep(0.05)  # a refusing server must not be hammered

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=max(0.0, t_end - time.monotonic()) + 5.0)
    return results
