"""Drives the program's serving engine on the wall clock.

The engine is driven through its public entry only: ``submit`` each
request when it falls due, then ``run(max_steps=1)``, one loop
iteration at a time.  After every iteration the harness stamps, with
the host clock, every output token that iteration produced; those
stamps are what a streaming client would see, and every end-to-end
metric is taken from them.

The engine's clock is injected (``WindowClock``) so that engine time is
seconds since the window opened: the engine's own request marks (queue
wait) are then on the harness's clock, and its idle fast-forward never
fires, because a request is submitted only once it is due.
"""

from __future__ import annotations

import contextlib
import dataclasses
import sys
import time
from typing import Optional

import numpy as np

#: seconds the run waits, after the window, for the requests due in it
#: (on one v5e a decode tick of smollm-135m at 64 slots and a 2048-long
#: pool takes about 0.3 s, so a 512-token answer due at the close needs
#: some 155 s)
DRAIN_S = 200.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class WindowClock:
    """The engine's clock.  Before ``open`` it is the host's monotonic
    clock.  The engine takes its clock's first reading after ``reset``
    as its zero, so ``open(origin)`` makes that first reading 0 and
    every later one the seconds since ``origin``: engine time is then
    window time."""

    def __init__(self):
        self.origin: Optional[float] = None
        self._first = False

    def open(self, origin: float) -> None:
        self.origin = origin
        self._first = True

    def __call__(self) -> float:
        if self.origin is None:
            return time.perf_counter()
        if self._first:
            self._first = False
            return 0.0
        return time.perf_counter() - self.origin


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache, by
    JAX's own monitoring events (one listener for the process)."""

    #: events whose seconds count as compile time: tracing, lowering,
    #: and the backend compile or persistent-cache load
    TIMED = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration",
             "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.backend = 0        # compiled or loaded
        self.misses = 0         # compiled (not in the persistent cache)
        self.seconds = 0.0      # in the TIMED events
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend += 1
        if name in self.TIMED:
            self.seconds += secs

    def _event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple:
        return self.backend, self.misses

    def describe(self, since: tuple = (0, 0, 0.0)) -> str:
        return (f"{self.backend - since[0]} programs compiled or loaded "
                f"({self.misses - since[1]} compiled), "
                f"{self.seconds - since[2]:.3f} s in compile events")

    def mark(self) -> tuple:
        return self.backend, self.misses, self.seconds


@dataclasses.dataclass
class Rec:
    """One request as the client sees it."""

    req: object                  # the engine's Request
    due: float                   # host time it fell due
    plen: int
    stamps: list = dataclasses.field(default_factory=list)
    seen: int = 0
    rejected: bool = False

    @property
    def done(self) -> bool:
        return len(self.stamps) >= self.req.max_new_tokens


@dataclasses.dataclass
class Window:
    origin: float
    end: float
    recs: list                   # every request due in the window
    ticks: list                  # (host time, [context of each row])
    steps: int
    run_s: float                 # host seconds inside engine.run
    compiles: tuple              # (compiled or loaded, compiled) in window
    lateness: list               # sent - due, seconds
    drained_s: float
    drain_end: float = 0.0       # host time the drain stopped
    closed: float = 0.0          # the step boundary at which it closed
    prefill_s: float = 0.0       # engine counters at the close
    decode_s: float = 0.0
    traced: Optional[tuple] = None   # (start, stop) of the profiler
    report_s: float = 0.0        # one engine.report() at the window's end


def _annotate(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


class Driver:
    """One engine, its warm-up, and its measured windows."""

    def __init__(self, engine, clock: WindowClock, counter: CompileCounter,
                 host_spans: bool = False):
        self.engine = engine
        self.clock = clock
        self.counter = counter
        self.span = _annotate(host_spans)
        self.active: list = []
        self.ticks: list = []
        self.steps = 0
        self.run_s = 0.0

    # -- warm-up ----------------------------------------------------------

    def _serve(self, reqs) -> None:
        """Submit ``(prompt, max_new)`` pairs at once and serve them."""
        for prompt, n in reqs:
            self.engine.submit(list(prompt), max_new_tokens=int(n))
        self.engine.run()

    def warm(self, sched, vocab: int) -> dict:
        """Compile, before the window, every program the window can use.

        The engine compiles its steps per bucket, and its admission path
        runs eager array updates whose shapes follow each prompt's
        length and the pool's length, and whose inputs are placed one
        way on a fresh or grown pool and another once a decode step has
        written it.  So, from a reset engine:

        1. the window's opening seconds are replayed on the wall clock,
           up to a second past its last pool growth (the growth order is
           fixed by the schedule), so the writes and steps that meet a
           fresh or just-grown pool meet it as they will in the window;
        2. the pool is walked up every length of its lattice that the
           schedule's requests can reach, and at each every prompt
           length of the schedule that fits is written into it.

        Every seed has the same set of requests, so step 2 does the same
        work for every seed; then ``reset``."""
        eng = self.engine
        q = eng.spec.quantize
        rng = np.random.default_rng(0)
        pairs = sorted(set(sched.pairs()))
        # the shortest output each prompt length comes with: a write's
        # shapes follow the prompt length and the pool length only
        least: dict = {}
        for p, o in pairs:
            least[p] = min(o, least.get(p, o))
        top = max(q(p + o) for p, o in pairs)
        chain = [lv for lv in eng.spec.lattice() if lv <= top]
        level, grown_at = q(1), 0.0
        for r in sched.requests:
            lv = q(len(r.prompt) + r.max_new)
            if lv > level:
                level, grown_at = lv, r.due

        def toks(n):
            return rng.integers(0, vocab, int(n))

        def grow(level: int) -> None:
            # one request whose need quantizes to ``level``
            p = level // 2
            n = min(max(2, level // 2 + 1 - p), level - p)
            self._serve([(toks(p), n)])

        def writes(level: int) -> None:
            self._serve([(toks(p), 2) for p, o in sorted(least.items())
                         if q(p + o) <= level])

        def phase(name: str, t: float, c: tuple) -> None:
            log(f"warm-up {name}: {time.perf_counter() - t:.3f} s, "
                f"{self.counter.describe(c)}")

        t0 = time.perf_counter()
        t, c = t0, self.counter.mark()
        eng.reset()
        self.window(sched, min(grown_at + 1.0, sched.requests[-1].due),
                    drain=False)
        phase("replay of the window's opening", t, c)
        eng.reset()
        for i, level in enumerate(chain):
            t, c = time.perf_counter(), self.counter.mark()
            if i:
                grow(level)
            writes(level)
            phase(f"writes at pool {level}", t, c)
        eng.reset()
        return {"warm_s": time.perf_counter() - t0, "chain": chain,
                "prompt_lengths": len(least)}

    # -- the window -------------------------------------------------------

    def _submit(self, r, due: float) -> "Rec":
        with self.span("bench.submit"):
            req = self.engine.submit(list(r.prompt), max_new_tokens=r.max_new,
                                     arrival=due - self.clock.origin)
        rec = Rec(req=req, due=due, plen=len(r.prompt),
                  rejected=bool(req.rejected))
        if not rec.rejected:
            self.active.append(rec)
        return rec

    def _step(self) -> float:
        t0 = time.perf_counter()
        with self.span("bench.run_step"):
            self.engine.run(max_steps=1)
        t = time.perf_counter()
        self.run_s += t - t0
        self.steps += 1
        with self.span("bench.observe"):
            self._observe(t)
        return t

    def _observe(self, t: float) -> None:
        still, ctxs = [], []
        for rec in self.active:
            g = len(rec.req.generated)
            if g > rec.seen:
                # token k >= 1 came from a decode tick that read the
                # prompt plus k earlier tokens
                ctxs.extend(rec.plen + k for k in range(max(rec.seen, 1), g))
                rec.stamps.extend([t] * (g - rec.seen))
                rec.seen = g
            if not rec.done:
                still.append(rec)
        self.active = still
        if ctxs:
            self.ticks.append((t, ctxs))

    def _sleep_until(self, t: float) -> None:
        dt = t - time.perf_counter()
        if dt > 0:
            with self.span("bench.sleep"):
                time.sleep(dt)

    def window(self, sched, seconds: float, profile_s: float = 0.0,
               profile_dir: Optional[str] = None,
               drain: bool = True) -> Window:
        """Serve ``sched`` for ``seconds`` of wall time, then drain the
        requests due in it.  With ``profile_s`` the JAX profiler records
        the window's last ``profile_s`` seconds into ``profile_dir``."""
        import jax

        self.active, self.ticks, self.steps, self.run_s = [], [], 0, 0.0
        origin = time.perf_counter()
        self.clock.open(origin)
        end = origin + seconds
        c0 = self.counter.snapshot()
        recs, lateness = [], []
        prof_at = end - profile_s if profile_s > 0 else None
        traced = None
        pending = sched.requests
        i = 0
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            if prof_at is not None and traced is None and now >= prof_at:
                jax.profiler.start_trace(profile_dir)
                traced = (time.perf_counter(), None)
            while i < len(pending) and origin + pending[i].due <= now:
                due = origin + pending[i].due
                recs.append(self._submit(pending[i], due))
                lateness.append(now - due)
                i += 1
            if not self.engine.scheduler.idle:
                self._step()
            elif i < len(pending):
                nxt = origin + pending[i].due
                if prof_at is not None and traced is None:
                    nxt = min(nxt, prof_at)
                self._sleep_until(min(nxt, end))
            else:
                self._sleep_until(end if prof_at is None or traced
                                  else min(prof_at, end))
        if traced is not None:
            traced = (traced[0], time.perf_counter())
            jax.profiler.stop_trace()
        c1 = self.counter.snapshot()
        closed = time.perf_counter()
        m = self.engine.metrics
        prefill_s, decode_s = m.prefill_s, m.decode_s
        steps, run_s = self.steps, self.run_s
        t0 = time.perf_counter()
        self.engine.report()
        report_s = time.perf_counter() - t0
        # drain: the requests due in the window finish; nothing new is sent
        d0 = time.perf_counter()
        while drain and self.active and time.perf_counter() - d0 < DRAIN_S:
            self._step()
        drain_end = time.perf_counter()
        drained = drain_end - d0
        return Window(origin=origin, end=end, recs=recs,
                      ticks=list(self.ticks),
                      steps=steps, run_s=run_s,
                      compiles=(c1[0] - c0[0], c1[1] - c0[1]),
                      lateness=lateness, drained_s=drained,
                      drain_end=drain_end, closed=closed,
                      prefill_s=prefill_s, decode_s=decode_s, traced=traced,
                      report_s=report_s)
