"""Faults planted under the timed path, for the tests that show the
check catches them (tests/bench/test_bench_faults.py) and for the chip
runs that read them at a cell's own size (bench/prove.py).

Serving: a decode step that returns its cache unchanged; a served token
altered where it is produced. Training: a step that returns its state
unchanged; half of each batch left out, the mean taken over the rest.
"""

from __future__ import annotations


def serve_state_unchanged(engine):
    """The engine's one-token step computes its logits but hands back
    the cache it was given."""
    import jax
    from repro.training import train_loop as TL
    step = jax.jit(TL.make_serve_step(engine.cfg, policy=engine.policy))
    engine._step = lambda p, tok, pos, cache: (step(p, tok, pos, cache)[0],
                                               cache)


def serve_token_altered(engine, every: int = 40):
    """Every `every`-th token the sampler produces is replaced by the
    next id."""
    inner = engine.sampler
    n = [0]
    vocab = engine.cfg.vocab

    def sample(row):
        tok = inner(row)
        n[0] += 1
        return (tok + 1) % vocab if n[0] % every == 0 else tok
    engine.sampler = sample


def train_state_unchanged(make):
    """A step factory whose step returns the state it was given."""
    def factory(cfg, opt):
        inner = make(cfg, opt)

        def step(state, batch):
            _, met = inner(state, batch)
            return state, met
        return step
    return factory


def train_half_batch(make):
    """A step factory whose step drops the second half of every batch
    and takes the mean over the rest."""
    def factory(cfg, opt):
        inner = make(cfg, opt)

        def step(state, batch):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return inner(state, half)
        return step
    return factory
