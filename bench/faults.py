"""Faults planted under the timed path, for the benchmark's own tests.

Each takes the built engines and the vocabulary size and breaks the
served path the way a faulty change to the program could; a run with a
fault planted has to come out `correct: false`.  Serving on one chip
can have two of them: a token altered where it is produced, and a step
that returns its decode state (the KV pool) unchanged.
"""
from __future__ import annotations

ALTERED_INDEX = 3           # the served token of each request altered


def altered_token(engines, vocab: int) -> None:
    """Token ALTERED_INDEX of each request is replaced by its successor
    id as the engine emits it (and fed back as the next input)."""
    for eng in engines:
        def emit(req, token, now, decode=True, row=None, _orig=eng._emit):
            if len(req.out_tokens) == ALTERED_INDEX:
                token = (token + 1) % vocab
            return _orig(req, token, now, decode=decode, row=row)
        eng._emit = emit


def unchanged_state(engines, vocab: int) -> None:
    """Every serve step computes on a copy of the KV pools and hands the
    old pools back, so no step's K and V rows are kept."""
    import jax
    import jax.numpy as jnp
    for eng in engines:
        def step(params, state, *rest, _orig=eng._step_fn):
            logits, _ = _orig(params, jax.tree.map(jnp.copy, state), *rest)
            return logits, state
        eng._step_fn = step


FAULTS = {"altered_token": altered_token, "unchanged_state": unchanged_state}
