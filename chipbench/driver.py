"""Wave driver: one wave is ``slots`` requests submitted together to a fresh
``Engine`` and stepped until every one has finished.

Only requests that start together at position 0 of a fresh cache are served
correctly by the engine (one position is shared by all slots), so a wave is
the traffic it serves today: offline batch generation.  The host spans
(``jax.profiler.TraceAnnotation``) name what the host was doing in a
device trace: building the engine, admitting, each engine step, and the
jitted step call inside it.  Given the names of instruments (``counters``),
a wave's engine is built with a ``repro.obs.metrics.MetricsRegistry`` and
the wave keeps what those instruments read at its end.
"""
import dataclasses
import gc
import time
from typing import Callable, List, Optional, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from repro.obs.metrics import MetricsRegistry


@dataclasses.dataclass
class Wave:
    prompt_len: np.ndarray      # per request, slot order
    gen_len: np.ndarray
    t_start: float              # wave start (engine build)
    t_submit: float
    t_end: np.ndarray           # host clock after each engine step
    inflight: np.ndarray        # occupied slots before each step
    done: list                  # (prompt, served tokens) of finished requests
    failed: int                 # requests not finished with all their tokens
    # name -> the instrument's ``as_json()`` at the wave's end, for each
    # instrument asked for that the engine made
    counters: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self):
        return float(self.t_end[-1] - self.t_start)


def occupancy(prompt_len, gen_len, steps):
    """Occupied slots and prompt-fed slots at each step of a wave: request r
    holds its slot for steps 0 .. P + G - 2 and is fed its prompt for steps
    0 .. P - 1."""
    s = np.arange(steps)[:, None]
    occ = (s <= (prompt_len + gen_len - 2)[None]).sum(1)
    pre = (s <= (prompt_len - 1)[None]).sum(1)
    return occ, pre


def run(serve, cfg, params, requests, max_len,
        on_step: Optional[Callable[[int], None]] = None,
        max_steps: Optional[int] = None,
        counters: Optional[Sequence[str]] = None) -> Wave:
    """Serve one wave of ``requests`` ((prompt ids, generation length), in
    slot order) on a fresh engine; ``on_step(n)`` runs after step n.  With
    ``max_steps`` (the warm-up) the wave stops early and counts as failed.
    With ``counters`` the engine gets a metrics registry, and the wave keeps
    those of its instruments that the engine made."""
    t_start = time.perf_counter()
    with TraceAnnotation("wave.build"):
        reg = None if counters is None else MetricsRegistry()
        eng = serve.Engine(cfg, params, len(requests), max_len, metrics=reg)
    step = eng._step

    def jit_call(*args):
        with TraceAnnotation("serve_step.call"):
            return step(*args)

    eng._step = jit_call
    reqs = [serve.Request(i, p, g) for i, (p, g) in enumerate(requests)]
    t_submit = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    with TraceAnnotation("engine.admit"):
        eng.admit()
    t_end: List[float] = []
    inflight: List[int] = []
    while max_steps is None or len(t_end) < max_steps:
        n = eng.inflight
        if not n:
            break
        inflight.append(n)
        with TraceAnnotation("engine.step"):
            eng.step()
        t_end.append(time.perf_counter())
        if on_step is not None:
            on_step(len(t_end))
    p = np.array([len(r.prompt) for r in reqs])
    g = np.array([r.gen for r in reqs])
    ok = [r for r in eng.done if r.reason == "finished" and len(r.out) == r.gen]
    failed = len(reqs) - len(ok)
    occ, _ = occupancy(p, g, len(t_end))
    if eng.pos > max_len or not np.array_equal(occ, inflight):
        failed = len(reqs)       # truncated, or not the wave's schedule
    read = {} if reg is None else \
        {n: reg.get(n).as_json() for n in counters if n in reg.names()}
    # the engine is freed by the cycle collector only (it holds bound
    # methods of itself): collect it now, so one cache pair is ever live
    del eng, jit_call, step
    gc.collect()
    return Wave(p, g, t_start, t_submit, np.array(t_end), np.array(inflight),
                [(r.prompt, list(r.out)) for r in ok], failed, read)
