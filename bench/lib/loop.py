"""The closed-loop client: drives ``GenerationEngine.submit``/``step`` and
records every token's arrival on the client's clock.

The engine is synchronous, so the client sees a request's new tokens when
``step()`` returns: each gets that time stamp.  A client whose request
finished sends its next one at once (closed loop).  Per step the client
also keeps what the step did: its span, the decode tokens it produced and
the prompt tokens it prefilled (the engine's ``n_chunk_tokens`` counter;
which prompt positions those were follows from the engine's FIFO chunk
order, mirrored here), which ``lib/flops.py`` turns into required FLOPs.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field


@dataclass
class Record:
    client: int
    index: int
    prompt: tuple
    max_new: int
    sent: float
    req: object = None                          # the engine's Request
    times: list = field(default_factory=list)   # arrival of each token
    prefilled: int = 0                          # prompt tokens prefilled


@dataclass
class Step:
    t0: float
    t1: float
    decode_tokens: list      # positions of decode tokens produced
    first_tokens: int        # requests that got their first token
    prefill: list            # (start, n) prompt ranges prefilled
    finished_prompts: int    # prompts whose last chunk ran (logits rows)


# decode steps after the fill, before the window (see ClosedLoop.warm)
SETTLE_STEPS = 2


def null_span(name):
    return contextlib.nullcontext()


class ClosedLoop:
    def __init__(self, mix, engine, request_cls, span=null_span):
        self.mix, self.eng, self.Request = mix, engine, request_cls
        self.span = span
        self.records: list = []
        self.current: list = [None] * mix.clients
        self.steps: list = []
        self._fifo: list = []           # records with prompt left to fill
        self._chunk_tokens = engine.n_chunk_tokens

    def send(self, client: int, now: float) -> None:
        index = (self.current[client].index + 1
                 if self.current[client] is not None else 0)
        r = self.mix.request(client, index)
        rec = Record(client=client, index=index, prompt=r.prompt,
                     max_new=r.max_new, sent=now)
        rec.req = self.Request(prompt=list(r.prompt),
                               max_new_tokens=r.max_new)
        with self.span("bench.submit"):
            self.eng.submit(rec.req)
        self.records.append(rec)
        self._fifo.append(rec)
        self.current[client] = rec

    def start(self) -> None:
        now = time.perf_counter()
        for c in range(self.mix.clients):
            self.send(c, now)

    def _prefill_ranges(self) -> tuple:
        """Attribute the prompt tokens the engine prefilled since the last
        step to prompts in submission order (the engine's FIFO)."""
        n = self.eng.n_chunk_tokens - self._chunk_tokens
        self._chunk_tokens = self.eng.n_chunk_tokens
        ranges, done = [], 0
        while n > 0 and self._fifo:
            rec = self._fifo[0]
            k = min(n, len(rec.prompt) - rec.prefilled)
            ranges.append((rec.prefilled, k))
            rec.prefilled += k
            n -= k
            if rec.prefilled == len(rec.prompt):
                self._fifo.pop(0)
                done += 1
        return ranges, done

    def step(self) -> Step:
        t0 = time.perf_counter()
        with self.span("bench.step"):
            self.eng.step()
        t1 = time.perf_counter()
        with self.span("bench.harvest"):
            ranges, done = self._prefill_ranges()
            decode, first = [], 0
            for c, rec in enumerate(self.current):
                n = len(rec.req.out_tokens)
                for k in range(len(rec.times), n):
                    rec.times.append(t1)
                    if k == 0:
                        first += 1
                    else:
                        decode.append(len(rec.prompt) + k - 1)
                if rec.req.done:
                    self.send(c, t1)
        s = Step(t0=t0, t1=t1, decode_tokens=decode, first_tokens=first,
                 prefill=ranges, finished_prompts=done)
        self.steps.append(s)
        return s

    def warm(self, page_size: int) -> None:
        """Warm the engine through ``submit``/``step`` alone, then fill it
        with the clients.

        One throwaway request (two full pages, two tokens) runs to its end
        first, so admission, cold-page coding, the decode step, finishing
        and page release have all run once; then every client sends its
        first request, the engine steps until each has its first token,
        and ``SETTLE_STEPS`` more steps run the full batch's decode step
        with the cold pool, so the window compiles nothing."""
        w = self.Request(prompt=[1] * (2 * page_size + 1), max_new_tokens=2)
        self.eng.submit(w)
        while not w.done:
            self.eng.step()
        self._chunk_tokens = self.eng.n_chunk_tokens
        self.start()
        first = list(self.current)
        while not all(r.times for r in first):
            self.step()
        for _ in range(SETTLE_STEPS):
            self.step()

    def run(self, seconds: float) -> tuple:
        """Step for ``seconds`` (the last step runs to its end); returns
        the window ``(t0, t1)``."""
        t0 = time.perf_counter()
        while True:
            s = self.step()
            if s.t1 - t0 >= seconds:
                return t0, s.t1
