"""The closed loop: clients, warm-up, the measured window, and its log.

Each client sends its next request when the last one completes.  The
loop drives ``ContinuousBatchingEngine.step``; every step ends in a host
read of the step's logits, so the host clock read after it is the time
at which the step's tokens reach the client.  Tokens, submissions and
completions are timed on that clock, and each step's program work is
logged (chunk width and start, decode contexts) for the per-layer
metrics.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Tracked:
    """One submitted request as the client sees it."""

    client: int
    index: int
    prompt: List[int]
    max_new_tokens: int
    handle: object
    submit_t: float
    token_t: List[float] = dataclasses.field(default_factory=list)
    end_t: Optional[float] = None


@dataclasses.dataclass
class StepWork:
    """What one engine step computed (from the clients' view)."""

    t0: float
    t1: float
    chunk: Optional[tuple]  # (start, real tokens, prompt length) of a prompt chunk
    decode: List[int]  # keys attended by each slot that decoded a token


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    tracked: List[Tracked]
    steps: List[StepWork]  # steps inside the window
    counters0: dict
    counters1: dict
    compiles: int  # backend compiles seen inside the window

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def in_window(self, t: float) -> bool:
        return self.t_open < t <= self.t_close

    def finished(self) -> List[Tracked]:
        return [r for r in self.tracked if r.end_t is not None and self.in_window(r.end_t)]


def counters(eng) -> dict:
    s = eng.stats
    return dict(
        steps=s.steps, decode_steps=s.decode_steps,
        active_slot_steps=s.active_slot_steps, idle_slot_steps=s.idle_slot_steps,
        prefill_tokens=s.prefill_tokens, prefill_padding=s.prefill_padding,
        generated_tokens=s.generated_tokens,
    )


class CompileCount:
    """Backend compiles, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class ClosedLoop:
    def __init__(self, eng, traffic, compiles: CompileCount, spans: bool = False,
                 clock=time.perf_counter):
        self.eng, self.traffic, self.spans, self.clock = eng, traffic, spans, clock
        self.compiles = compiles
        self.tracked: List[Tracked] = []
        self.live: Dict[int, Tracked] = {}  # rid -> request in flight
        self.next_index = [0] * traffic.mix.clients

    def submit(self, client: int) -> None:
        req = self.traffic.request(client, self.next_index[client])
        self.next_index[client] += 1
        with _span("bench.submit", self.spans):
            t = self.clock()
            h = self.eng.submit(req.prompt, max_new_tokens=req.max_new_tokens)
        tr = Tracked(client, req.index, req.prompt, req.max_new_tokens, h, t)
        self.tracked.append(tr)
        self.live[h.rid] = tr

    def step(self) -> StepWork:
        before = {rid: (tr.handle.prefill_pos, len(tr.handle.output))
                  for rid, tr in self.live.items()}
        t0 = self.clock()
        with _span("engine.step", self.spans):
            done = self.eng.step()
        t1 = self.clock()
        chunk, decode = None, []
        for rid, tr in self.live.items():
            pos0, out0 = before[rid]
            h = tr.handle
            pos1, out1 = h.prefill_pos, len(h.output)
            plen = len(tr.prompt)
            if pos1 > pos0:
                chunk = (pos0, pos1 - pos0, plen)
            for j in range(out0, out1):
                tr.token_t.append(t1)
                if j > 0:  # token 0 comes from the prefill logits
                    decode.append(plen + j)
        for req in done:
            tr = self.live.pop(req.rid)
            tr.end_t = t1
            self.submit(tr.client)
        return StepWork(t0, t1, chunk, decode)

    def warm_up(self, widths: List[int], rng_seed: int = 0) -> None:
        """Run every chunk width, the decode step and the block scrub once.

        A prompt one token short of a chunk width that ends at its
        first token leaves a padded position to scrub at retirement.
        """
        rng = np.random.default_rng(rng_seed)
        vocab = self.traffic.vocab
        lengths = [(max(w - 1, 1), 1) for w in widths] + [(widths[0], 2)]
        hs = [self.eng.submit(rng.integers(0, vocab, n).tolist(), max_new_tokens=m)
              for n, m in lengths]
        while any(h.state.name not in ("FINISHED", "CANCELLED") for h in hs):
            self.eng.step()

    def run(self, seconds: float, steps: Optional[int] = None) -> Window:
        """Fill every slot, then measure ``seconds`` (rounded up to a step),
        or exactly ``steps`` engine steps where that is given."""
        for c in range(self.traffic.mix.clients):
            self.submit(c)
        first = list(self.tracked)
        while not all(tr.token_t for tr in first):
            self.step()
        n_compiles = self.compiles.count
        t_open, c0 = self.clock(), counters(self.eng)
        log = []
        with _span("bench.window", self.spans):
            while True:
                work = self.step()
                log.append(work)
                if (len(log) == steps) if steps else (work.t1 - t_open >= seconds):
                    break
        return Window(t_open, log[-1].t1, self.tracked, log, c0,
                      counters(self.eng), self.compiles.count - n_compiles)
