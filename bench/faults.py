"""Faults planted in the timed path, to show that the check catches them.

Each fault takes the engine before the window and wraps its decode call.
The harness's own tests run them at a tiny width on the CPU, and
``control.py --fault <name>`` runs them at a cell's own size on the chip.
No benchmark run installs one.
"""

import jax
import jax.numpy as jnp


def alter_token(eng):
    """A token altered where it is produced: the decode step's logits put
    the lowest-scoring token first for row 0."""
    decode = eng._decode

    def broken(*args):
        logits, caches = decode(*args)
        row = logits[0]
        return logits.at[0].set(-row), caches
    eng._decode = broken


def state_unchanged(eng):
    """A step that returns its state unchanged: decode hands back a copy of
    the cache it was given, so no decoded token's keys and values are kept.
    Decode consumes (donates) the cache it is passed, so the copy is taken
    before the call."""
    decode = eng._decode

    def broken(params, toks, caches, pos):
        kept = jax.tree.map(jnp.copy, caches)
        logits, _ = decode(params, toks, caches, pos)
        return logits, kept
    eng._decode = broken


def half_batch(eng):
    """Half of the batch left out: rows in the second half of the batch get
    the first half's logits instead of their own."""
    decode = eng._decode

    def broken(*args):
        logits, caches = decode(*args)
        b = logits.shape[0] // 2
        return jnp.concatenate([logits[:b], logits[:b]]), caches
    eng._decode = broken


FAULTS = {f.__name__: f for f in (alter_token, state_unchanged, half_batch)}
