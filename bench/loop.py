"""Drives the fabric: warm-up, the measured window, the drain.

Every request enters through ``Fabric.submit`` and the loop drives
``Fabric.step``. After each step the harness reads the requests in the
engines' lanes (``Fabric.engines[*].active``) and those the step
completed; a token's time is the host clock when the step that produced
it returned (a step ends in a host read of its tokens). Times are seconds
after the window opened.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from bench import generator


@dataclasses.dataclass
class Track:
    """What the client side saw of one request."""
    req: generator.Request
    due: float
    submit: float
    uid: Optional[int] = None            # None: refused at submit
    token_times: List[float] = dataclasses.field(default_factory=list)
    done: Optional[float] = None
    output: Optional[List[int]] = None
    preemptions: int = 0
    times_done: int = 0


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    prefills: int          # requests laned (and prefilled) in this step
    decodes: int           # lanes that decoded a token in this step
    context: List[int]     # decoding lanes' lengths before the step
    prompts: List[int]     # prompt lengths of the requests laned in it
    kv_used: Optional[float] = None


class Driver:
    """One fabric, one clock; ``annotate`` wraps host phases in profiler
    annotations (traced runs only)."""

    def __init__(self, fabric, *, annotate: bool = False, kv_probe=None):
        self.fab = fabric
        self.tracks: List[Track] = []
        self.by_uid: Dict[int, Track] = {}
        self.steps: List[Step] = []
        self._lanes: set = set()
        self._annotate = annotate
        self._kv_probe = kv_probe
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def _span(self, name: str):
        if not self._annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def submit(self, req: generator.Request, due: Optional[float] = None):
        with self._span("bench.submit"):
            t = self.now()
            uid = self.fab.submit(req.prompt.tolist(), max_new_tokens=req.max_new)
        tr = Track(req, t if due is None else due, t, uid)
        self.tracks.append(tr)
        if uid is not None:
            self.by_uid[uid] = tr
        return tr

    def wait_until(self, t: float) -> None:
        with self._span("bench.wait"):
            dt = t - self.now()
            if dt > 0:
                time.sleep(dt)

    def _in_lanes(self):
        return [r for eng in self.fab.engines for r in eng.active if r is not None]

    def step(self) -> List[Track]:
        before = {r.uid: len(r.output) for r in self._in_lanes()}
        t0 = self.now()
        with self._span("bench.step"):
            done = self.fab.step()
        t1 = self.now()
        lanes = self._in_lanes()
        seen = lanes + list(done)
        finished = []
        for r in seen:
            tr = self.by_uid[r.uid]
            new = len(r.output) - len(tr.token_times)
            if new > 0:
                tr.token_times.extend([t1] * new)
        for r in done:
            tr = self.by_uid[r.uid]
            tr.done, tr.output, tr.preemptions = t1, list(r.output), r.preemptions
            tr.times_done += 1
            finished.append(tr)
        laned = [len(r.prompt) for r in seen if r.uid not in self._lanes]
        # a lane that was laned before the step decodes at its length then:
        # prompt + tokens so far - 1 (the last token is the step's input)
        context = [len(self.by_uid[u].req.prompt) + n - 1
                   for u, n in before.items() if n > 0]
        context += [len(r.prompt) for r in seen if r.uid not in before]
        self._lanes = {r.uid for r in lanes}
        kv = self._kv_probe() if self._kv_probe is not None else None
        self.steps.append(Step(t0, t1, len(laned), len(seen), context, laned, kv))
        return finished

    def idle(self) -> bool:
        return self.fab.idle()


def warm_up(fabric, prompt_lens, vocab: int, seed: int) -> None:
    """Serve one request of every prompt length the traffic sends (each is
    a prefill program) through the fabric, and decode with them."""
    rng = generator.rng_for(seed, 7)
    for n in sorted(set(prompt_lens)):
        fabric.submit(rng.integers(1, vocab, size=n, dtype=np.int32).tolist(),
                      max_new_tokens=2)
    until_idle(fabric)


def until_idle(fabric, limit_s: float = 300.0) -> None:
    """Step until the fabric holds no request; a set-up that does not end
    within ``limit_s`` is an error, not a hang."""
    t0 = time.perf_counter()
    while not fabric.idle():
        fabric.step()
        if time.perf_counter() - t0 > limit_s:
            raise RuntimeError(f"the fabric did not drain its set-up requests in {limit_s:.0f}s")


def cover_shapes(shadow, prompt_lens, out_lens, max_batch: int,
                 page_size: int, num_pages: int, vocab: int) -> None:
    """Drive ``shadow`` -- a fabric of the same serving geometry over a tiny
    model -- through every shape of the engine's host-side array work that
    the traffic can reach, so none is first built inside the window: page
    growth for 1..max_batch lanes at once, and the retirement of every page
    count a request of these lengths can end with. Only the traffic's own
    prompt lengths are sent (one prefill program each); each page count is
    reached by decoding. Waves stay within the lanes and half the pool, so
    the shadow never preempts."""
    rng = generator.rng_for(0, 8)
    lens = sorted(set(prompt_lens))

    def serve(wave):
        for n, out in wave:
            shadow.submit(rng.integers(1, vocab, size=n, dtype=np.int32).tolist(),
                          max_new_tokens=out)
        until_idle(shadow)

    for k in range(1, max_batch + 1):
        serve([(lens[0], 2)] * k)
    # a prompt of n tokens with o tokens out ends holding ceil((n + o - 1) /
    # page_size) pages: for each count, the request that reaches it soonest
    ends = {}
    for n in lens:
        for o in sorted(set(out_lens)):
            used = -(-(n + o - 1) // page_size)
            if used not in ends or o < ends[used][1]:
                ends[used] = (n, o)
    wave, pages = [], 0
    for used, (n, o) in sorted(ends.items(), key=lambda kv: kv[1][1]):
        if len(wave) == max_batch or pages + used > (num_pages - 1) // 2:
            serve(wave)
            wave, pages = [], 0
        wave.append((n, o))
        pages += used
    serve(wave)


def run_open(drv: Driver, reqs, seconds: float) -> None:
    """Submit each request when it is due and step while there is work,
    until the window closes; requests due in it but not yet submitted are
    submitted then (the caller drains)."""
    drv.t0 = time.perf_counter()
    nxt, n = 0, len(reqs)
    while True:
        now = drv.now()
        if now >= seconds:
            break
        while nxt < n and reqs[nxt].due <= now:
            drv.submit(reqs[nxt], due=reqs[nxt].due)
            nxt += 1
        if drv.idle():
            drv.wait_until(min(reqs[nxt].due if nxt < n else seconds, seconds))
            continue
        drv.step()
    while nxt < n:  # due inside the window, but not yet submitted
        drv.submit(reqs[nxt], due=reqs[nxt].due)
        nxt += 1


def run_closed(drv: Driver, reqs, seconds: float, outstanding: int) -> None:
    """Keep ``outstanding`` requests in the fabric: one submitted for each
    completed. The window closes at ``seconds``; requests in flight then
    are not waited for."""
    drv.t0 = time.perf_counter()
    it = iter(reqs)
    for _ in range(outstanding):
        drv.submit(next(it))
    while drv.now() < seconds:
        for _ in drv.step():
            drv.submit(next(it))
