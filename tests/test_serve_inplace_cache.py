"""The served step updates its K/V cache in place: the cache is donated to
the step, and a step changes only the new token's row of each layer and
slot (the ring slot ``pos mod L`` of a sliding-window layer)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import resilience as RES
from repro.launch import serve
from repro.launch.serve import Engine, Request
from repro.models import decode, get_config
from repro.models import params as MP
from repro.obs import SpanTracer, spans as SP


def _cfg(name):
    if name == "qwen2-0.5b-int8":
        return dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                                   kv_cache_dtype="int8")
    return get_config(name).reduced()


def _random_cache(cfg, batch, max_len, seed):
    """A cache of the step's shapes filled with noise, so that every entry
    a step leaves alone can be told apart from one it writes."""
    rng = np.random.default_rng(seed)

    def fill(spec):
        if spec.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, spec.shape), jnp.int8)
        if spec.dtype == jnp.float32:       # int8 scales
            return jnp.asarray(rng.uniform(1e-3, 2e-2, spec.shape),
                               jnp.float32)
        return jnp.asarray(rng.normal(size=spec.shape), spec.dtype)
    return jax.tree.map(fill, decode.cache_specs(cfg, batch, max_len))


def test_step_takes_the_donated_cache_and_aliases_it():
    cfg = _cfg("qwen2-0.5b")
    params = MP.init_params(cfg, seed=0)
    cache = decode.init_cache(cfg, params, 2, 16)
    step = decode.make_serve_step(cfg)
    tok = jnp.ones((2, 1), jnp.int32)
    pos = jnp.asarray(0, jnp.int32)
    mem = step.lower(params, cache, tok, pos).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= decode.cache_num_bytes(cache)
    _, new = step(params, cache, tok, pos)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(cache))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(new))


# (config, cache length, first position): the ring case starts past the
# reduced sliding window (16), so its local layers wrap
CASES = [("qwen2-0.5b", 12, 3), ("qwen2-0.5b-int8", 12, 3),
         ("gemma2-27b", 24, 17)]


@pytest.mark.parametrize("name,max_len,first", CASES,
                         ids=[c[0] for c in CASES])
def test_two_steps_write_one_row_per_layer_and_slot(name, max_len, first):
    cfg = _cfg(name)
    params = MP.init_params(cfg, seed=0)
    batch = 3
    cache = _random_cache(cfg, batch, max_len, seed=1)
    step = decode.make_serve_step(cfg)
    tok = jnp.asarray([[5], [7], [11]], jnp.int32)
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(cache)[0]]
    ring = False
    for pos in (first, first + 1):
        before = [np.asarray(leaf) for leaf in jax.tree.leaves(cache)]
        _, cache = step(params, cache, tok, jnp.asarray(pos, jnp.int32))
        after = [np.asarray(leaf) for leaf in jax.tree.leaves(cache)]
        for path, old, new in zip(paths, before, after):
            length = old.shape[-2]
            row = pos % length
            ring |= row != pos
            keep = np.ones(length, bool)
            keep[row] = False
            # every layer and slot: all rows but one bit-equal, that one new
            np.testing.assert_array_equal(new[..., keep, :], old[..., keep, :],
                                          err_msg=path)
            changed = (new[..., row, :] != old[..., row, :]).any(-1)
            assert changed.all(), path
    assert ring == (name == "gemma2-27b")


def _engine(slots, res, spans=None):
    cfg = _cfg("qwen2-0.5b")
    params = MP.init_params(cfg, seed=0)
    eng = Engine(cfg, params, slots, 32, spans=spans, resilience=res)
    rng = np.random.default_rng(3)
    for rid in range(slots + 1):
        eng.submit(Request(rid, rng.integers(1, cfg.vocab_size, 3 + rid)
                           .astype(np.int32), 4))
    return eng


# retries with no backoff come back on the next tick, in request order, so
# a restarted batch is served as a run that never failed would serve it
NO_BACKOFF = RES.ResilienceConfig(seed=0, backoff_base=0, backoff_jitter=0)


def _fail_at(eng, monkeypatch, surfaces, step):
    """Make engine step ``step`` raise after the real serve step took the
    cache: when the step is called, or only when its results are read (the
    logits by the argmax, or the cache by the sync), as a fault on an
    accelerator surfaces."""
    def failing(real):
        def call(*args):
            if eng.steps == step:
                if surfaces == "step":
                    real(*args)
                raise RuntimeError(f"device fault, seen at {surfaces}")
            return real(*args)
        return call

    if surfaces == "step":
        eng._step = failing(eng._step)
    elif surfaces == "sample":
        monkeypatch.setattr(serve, "sample_guarded_argmax",
                            failing(serve.sample_guarded_argmax))
    else:
        monkeypatch.setattr(jax, "block_until_ready",
                            failing(jax.block_until_ready))


@pytest.mark.parametrize("surfaces", ["step", "sample", "sync"])
def test_step_that_fails_after_taking_the_cache_restarts_its_requests(
        surfaces, monkeypatch):
    """The step took the donated cache and then failed, where it was called
    or where its results were read: the engine builds a fresh cache,
    requeues the in-flight requests under the retry policy, and serves them
    from position 0 with the tokens of a run that never failed."""
    clean = _engine(2, NO_BACKOFF)
    clean.run()
    want = {r.rid: list(r.out) for r in clean.done}

    tr = SpanTracer()
    eng = _engine(2, NO_BACKOFF, spans=tr)
    _fail_at(eng, monkeypatch, surfaces, step=3)
    eng.admit()
    for _ in range(4):
        eng.step()
    assert eng.pos == 0 and eng.inflight == 0 and eng.retries == 2
    assert sorted((r.rid, r.attempt) for _, r in eng.delayed) == \
        [(0, 2), (1, 2)]
    assert [r.rid for r in eng.queue] == [2]
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(eng.cache))
    eng.run()
    assert {r.rid: list(r.out) for r in eng.done} == want
    assert SP.validate(tr.events, slots=2, engine_steps=eng.steps) == []


def test_a_step_that_always_fails_spends_the_retry_budget():
    """Restarts count as attempts: a fault on every step ends each request
    once it has had ``max_attempts`` tries, and the run ends."""
    tr = SpanTracer()
    eng = _engine(2, RES.ResilienceConfig(seed=0, max_attempts=2), spans=tr)
    real = eng._step

    def always_fails(*args):
        real(*args)
        raise RuntimeError("device fault")

    eng._step = always_fails
    for _ in range(64):                 # as Engine.run, but bounded
        if not (eng.queue or eng.inflight or eng.delayed):
            break
        eng.admit()
        eng.step()
    assert len(eng.done) == 3 and all(not r.out for r in eng.done)
    assert {r.reason for r in eng.done} == \
        {SP.TRUNCATED_PREFIX + RES.REASON_RETRY_EXHAUSTED}
    assert eng.retries == 3
    assert SP.validate(tr.events, slots=2, engine_steps=eng.steps) == []


def test_step_that_fails_before_taking_the_cache_keeps_it():
    res = RES.ResilienceConfig(seed=0)
    eng = _engine(2, res)
    real = eng._step

    def fails_on_step_1(*args):
        if eng.steps == 1:
            raise RuntimeError("refused before dispatch")
        return real(*args)

    eng._step = fails_on_step_1
    eng.admit()
    for _ in range(2):
        eng.step()
    assert eng.pos == 1 and eng.inflight == 2 and eng.retries == 0
    assert [r.fed for r in eng.slots] == [1, 1]
