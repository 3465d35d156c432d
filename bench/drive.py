"""Drive the engine with a request stream and record what happened.

One loop for both kinds of traffic: submit what is due, run one
``engine.step()``, then read each live request's public fields (slot,
tokens generated) and stamp the host clock. A closed-loop client submits
its next request when its last one finished; open-loop requests are due on
the stream's schedule, whether or not the engine keeps up, and each is
timed from when it was due.

For the per-layer metrics each step also records the live work it did:
the rows each running request fed and the tokens it then held. A request
that decodes feeds one row. A request that prefills feeds
``min(chunk, prompt left)`` rows, which is what the engine grants when no
``chunk_budget`` caps prompt rows; where the first token then comes at
another step than that predicts, or a request is preempted, the step
counts are marked inexact and the readers that need them stay silent.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, List, Optional

from bench import system

Annotate = Callable[[str], Any]


@dataclasses.dataclass
class Record:
    """One request's life, on the host clock."""

    req: Any                       # the program's Request
    due: float
    submitted: float
    prompt_len: int
    max_new: int
    client: Optional[int] = None
    admit_t: Optional[float] = None
    first_t: Optional[float] = None
    done_t: Optional[float] = None
    token_t: List[float] = dataclasses.field(default_factory=list)
    fed: int = 0                   # prompt tokens resident (hits included)
    width1_tokens: int = 0         # tokens made by the width-1 program


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    running: int                   # requests that took part in the step
    rows: int                      # rows fed, all requests
    live: list                     # (rows, resident tokens) per request
    width1: bool                   # no request fed more than one row, so
    #                                the engine ran its width-1 program


class Driver:
    """Feeds ``stream`` into ``eng`` and keeps a :class:`Record` per
    request and a :class:`Step` per engine step."""

    def __init__(self, eng, stream, traffic: dict, geometry: dict, *,
                 annotate: Annotate, clock=time.perf_counter):
        self.eng = eng
        self.stream = stream
        self.open = traffic["loop"] == "open"
        self.geometry = geometry
        self.annotate = annotate
        self.clock = clock
        self.records: List[Record] = []
        self.steps: List[Step] = []
        self.live: List[Record] = []
        self.exact = eng.scheduler.chunk_budget is None
        self._idle_clients: List[tuple] = []    # (client, due)
        self._next_due = math.inf
        self._t0 = 0.0
        self._preemptions = eng.paged_stats.preemptions

    # -- traffic ------------------------------------------------------------

    def start(self) -> None:
        """Traffic begins now: the requests in flight at the start (each
        closed-loop client's first) are submitted, cut to spread shares;
        the open loop's first arrival is scheduled."""
        self._t0 = self.clock()
        with self.annotate("bench.submit"):
            for i in range(self.stream.n_initial):
                self._submit(*self.stream.first_request(i), due=self._t0,
                             client=None if self.open else i)
        if self.open:
            self._next_due = self._t0 + self.stream.next_arrival()

    def _submit(self, prompt, max_new: int, *, due: float,
                client: Optional[int] = None) -> None:
        req = system.submit(self.eng, prompt, max_new)
        rec = Record(req=req, due=due, submitted=self.clock(),
                     prompt_len=int(len(prompt)), max_new=int(max_new),
                     client=client)
        self.records.append(rec)
        self.live.append(rec)

    def _submit_due(self) -> None:
        now = self.clock()
        if self.open:
            if self._next_due > now:
                return
            with self.annotate("bench.submit"):
                while self._next_due <= now:
                    self._submit(*self.stream.next_request(),
                                 due=self._next_due)
                    self._next_due = self._t0 + self.stream.next_arrival()
        elif self._idle_clients:
            with self.annotate("bench.submit"):
                for c, due in self._idle_clients:
                    self._submit(*self.stream.next_request(), due=due,
                                 client=c)
            self._idle_clients = []

    # -- stepping -----------------------------------------------------------

    def tick(self, deadline: float = math.inf) -> None:
        """Submit what is due, then run one engine step; with nothing to
        run, wait for the next arrival (or ``deadline``)."""
        self._submit_due()
        if not self.eng.scheduler.has_work:
            wait = min(self._next_due, deadline) - self.clock()
            if 0 < wait < math.inf:
                with self.annotate("bench.idle"):
                    time.sleep(wait)
            return
        before = [(r, r.req.num_generated, r.req.slot is not None)
                  for r in self.live]
        t0 = self.clock()
        with self.annotate("bench.step"):
            self.eng.step()
        t1 = self.clock()
        with self.annotate("bench.record"):
            self._observe(before, t0, t1)

    def _observe(self, before, t0: float, t1: float) -> None:
        bs = self.geometry["block_size"]
        chunk = self.geometry["chunk_size"]
        live, rows_all, running = [], 0, 0
        still, made = [], []
        for rec, gen0, had_slot in before:
            req = rec.req
            if req.slot is None and not req.is_done():
                still.append(rec)          # still queued
                continue
            running += 1
            if not had_slot and rec.admit_t is None:
                rec.admit_t = t0
                rec.fed = req.n_prefix_hit * bs
            new = req.num_generated - gen0
            if gen0 == 0:                                  # prefilling
                rows = min(chunk, rec.prompt_len - rec.fed)
                rec.fed += rows
                if (new > 0) != (rec.fed == rec.prompt_len):
                    self.exact = False
                kv_len = rec.fed
            else:                                          # decoding
                rows, kv_len = 1, rec.prompt_len + gen0
            live.append((rows, kv_len))
            rows_all += rows
            rec.token_t.extend([t1] * new)
            made.append((rec, new))
            if new and rec.first_t is None:
                rec.first_t = t1
            if req.is_done():
                rec.done_t = t1
                if rec.client is not None:
                    self._idle_clients.append((rec.client, t1))
            else:
                still.append(rec)
        self.live = still
        if self.eng.paged_stats.preemptions != self._preemptions:
            self.exact = False
        width1 = all(rows <= 1 for rows, _ in live)
        if width1:
            for rec, new in made:
                rec.width1_tokens += new
        self.steps.append(Step(t0=t0, t1=t1, running=running, rows=rows_all,
                               live=live, width1=width1))


def warm_programs(eng, chunk: int, vocab: int) -> None:
    """Compile the step's two programs, width 1 and the chunk width (the
    sampling runs inside both), with two throwaway requests: a one-token
    prompt decodes at width 1, a prompt of ``chunk + 1`` tokens prefills at
    the chunk width."""
    for n_prompt, n_new in ((1, 2), (chunk + 1, 1)):
        system.submit(eng, [i % vocab for i in range(n_prompt)], n_new)
        while eng.scheduler.has_work:
            eng.step()
