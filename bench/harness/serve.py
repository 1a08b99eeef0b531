"""Client-side driving of a serving session.

One thread (``bench-stepper``) drives ``session.step()``; clients submit
from their own threads and read ``handle.stream(drive=False)``. Every time
below is ``time.perf_counter()`` taken on the client side: a token's time
is when its ``TokenChunk`` reached the client, a request's start is its
scheduled arrival (open loop) or its ``submit`` (closed loop).

Host spans go into the profiler's trace with ``TraceAnnotation`` (no cost
when no trace is being taken): ``bench.step`` (the stepper inside
``session.step()``), ``bench.no_request`` (the stepper idle, waiting for an
arrival), ``bench.submit`` and ``bench.stream_wait`` (a client submitting,
and waiting on its stream), and ``bench.window`` (the measured window).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional

from jax.profiler import TraceAnnotation
from repro.serving import ServingError

from bench.harness import traffic as traffic_mod

now = time.perf_counter


@dataclasses.dataclass
class Record:
    """What one client saw of one request."""
    draw: traffic_mod.Draw
    start: float = 0.0                 # scheduled arrival / submit time
    submitted: float = 0.0
    arrivals: List[tuple] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    result_tokens: Optional[List[int]] = None
    end: Optional[float] = None
    error: Optional[str] = None
    cancelled: bool = False
    late_s: float = 0.0                # generator lateness (open loop)

    @property
    def prompt_len(self) -> int:
        return len(self.draw.prompt)

    @property
    def first(self) -> Optional[float]:
        return self.arrivals[0][0] if self.arrivals else None

    @property
    def finished(self) -> bool:
        return (self.end is not None and self.error is None
                and not self.cancelled)


class Stepper:
    """The single thread that advances the session."""

    def __init__(self, session):
        self.session = session
        self.wake = threading.Event()
        # held around every step: a caller that holds it can submit
        # several requests that the next step admits as one wave
        self.lock = threading.Lock()
        self._stop = threading.Event()
        self.error: Optional[BaseException] = None
        self.thread = threading.Thread(target=self._loop, name="bench-stepper",
                                       daemon=True)

    def start(self):
        self.thread.start()

    def _loop(self):
        try:
            while True:
                with self.lock, TraceAnnotation("bench.step"):
                    progressed = self.session.step()
                if progressed:
                    continue
                if self._stop.is_set():
                    self.session.flush()
                    return
                with TraceAnnotation("bench.no_request"):
                    self.wake.wait(0.002)
                    self.wake.clear()
        except Exception as e:          # noqa: BLE001 — reported by run
            self.error = e
            self.session.close(e)       # resolve every waiting client

    def stop_when_idle(self):
        self._stop.set()
        self.wake.set()
        self.thread.join()
        if self.error is not None:
            raise self.error


def _consume(handle, rec: Record):
    with TraceAnnotation("bench.stream_wait"):
        for ev in handle.stream(drive=False):
            rec.arrivals.append((now(), len(ev.tokens)))
            rec.tokens.extend(ev.tokens)
    rec.end = now()
    if handle.error is not None:
        rec.error = repr(handle.error)
        return
    res = handle.result(drive=False)
    rec.cancelled = bool(res.cancelled)
    rec.result_tokens = list(res.tokens)


def _request(draw, Request):
    return Request(prompt_tokens=draw.prompt, max_new_tokens=draw.max_new,
                   request_id=f"r{draw.index}")


class Traffic:
    """Runs one mix against a session until :meth:`stop`."""

    def __init__(self, session, stepper: Stepper, mix: dict, vocab: int,
                 seed: int, Request):
        self.session, self.stepper, self.mix = session, stepper, mix
        self.vocab, self.seed, self.Request = vocab, seed, Request
        self.records: List[Record] = []
        self.handles = []
        self._lock = threading.Lock()
        self._stopping = threading.Event()
        self.threads: List[threading.Thread] = []
        self.t_start = 0.0

    def _submit(self, draw, start: float) -> None:
        rec = Record(draw=draw, start=start)
        req = _request(draw, self.Request)
        with TraceAnnotation("bench.submit"):
            rec.submitted = now()
            try:
                h = self.session.submit(req)
            except ServingError as e:    # refused (queue full, closed)
                rec.error = repr(e)
                rec.end = now()
                with self._lock:
                    self.records.append(rec)
                return None, rec
        self.stepper.wake.set()
        with self._lock:
            self.records.append(rec)
            self.handles.append(h)
        return h, rec

    def _client(self, c: int):
        for draw in traffic_mod.closed_stream(self.mix, self.vocab,
                                              self.seed, c):
            if self._stopping.is_set():
                return
            h, rec = self._submit(draw, now())
            if h is None:
                return
            _consume(h, rec)
            rec.start = rec.submitted

    def _generator(self):
        for draw in traffic_mod.open_stream(self.mix, self.vocab, self.seed):
            due = self.t_start + draw.at_s
            while True:
                left = due - now()
                if self._stopping.is_set():
                    return
                if left <= 0:
                    break
                time.sleep(min(left, 0.01))
            h, rec = self._submit(draw, due)
            rec.late_s = rec.submitted - due
            if h is None:
                continue
            t = threading.Thread(target=_consume, args=(h, rec),
                                 daemon=True)
            t.start()
            with self._lock:
                self.threads.append(t)

    def start(self):
        self.t_start = now()
        if self.mix["loop"] == "closed":
            targets = [(self._client, (c,))
                       for c in range(self.mix["clients"])]
        elif self.mix["loop"] == "open":
            targets = [(self._generator, ())]
        else:
            raise ValueError(f"unknown loop {self.mix['loop']!r}")
        for fn, args in targets:
            t = threading.Thread(target=fn, args=args, daemon=True)
            t.start()
            self.threads.append(t)

    def stop(self):
        """No new requests; cancel what is unfinished; wait for every
        client to see its request resolve."""
        self._stopping.set()
        with self._lock:
            handles = list(self.handles)
        for h in handles:
            h.cancel()
        self.stepper.wake.set()
        while True:
            with self._lock:
                alive = [t for t in self.threads if t.is_alive()]
                handles = list(self.handles)
            if not alive:
                break
            for h in handles:        # submitted after the first sweep
                h.cancel()
            for t in alive:
                t.join(timeout=0.5)
