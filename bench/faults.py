"""Faults planted under the timed path, to see ``correct`` come out false.

They are those a one-chip serving cell can have: a decode step that
returns its KV cache unchanged; half of the batch's rows answered from
nothing; a token altered where the logits are made; an adapter page that a
swap-in never writes, so the kernel reads a stale slot. A decode fault
wraps the model's ``decode_step`` (``decode(params, tokens, caches, pos,
start) -> (logits, caches)``); the page fault replaces the adapter
memory's ``_page_write``.
"""

from __future__ import annotations

import contextlib

import jax


def cache_unchanged(decode, params, tokens, caches, pos, start=None):
    logits, _ = decode(params, tokens, caches, pos, start)
    return logits, caches


def half_batch(decode, params, tokens, caches, pos, start=None):
    logits, new = decode(params, tokens, caches, pos, start)
    # the lowest rows: admission takes free rows lowest index first, so
    # these are the rows in use
    half = -(-logits.shape[0] // 2)
    return logits.at[:half].set(0.0), new


def token_altered(decode, params, tokens, caches, pos, start=None):
    logits, new = decode(params, tokens, caches, pos, start)
    return logits.at[0, -1, 7].add(1e4), new


DECODE_FAULTS = {"cache_unchanged": cache_unchanged,
                 "half_batch": half_batch, "token_altered": token_altered}
NAMES = sorted(DECODE_FAULTS) + ["page_not_written"]


@contextlib.contextmanager
def planted(engine, name: str):
    """Run ``engine`` with fault ``name`` planted under it, and take it
    out again on leaving (a page left unwritten stays stale)."""
    from repro.serving import memory

    saved = engine._decode, memory._page_write
    if name == "page_not_written":
        memory._page_write = lambda pool, page, starts: pool
    else:
        fault, decode = DECODE_FAULTS[name], engine.model.decode_step
        engine._decode = jax.jit(
            lambda p, t, c, pos, start=None: fault(decode, p, t, c, pos,
                                                   start))
    try:
        yield
    finally:
        engine._decode, memory._page_write = saved
