"""Faults planted under the timed path, each of which ``correct`` has to
catch: a test drives a whole run with one of them (``tests/bench``), and
``python3 -m bench.study --fault <name>`` reads it at a cell's own size.
Each takes the engine after set-up and breaks its jitted decode."""
import jax.numpy as jnp


def token_altered(engine):
    """Every decoded token is another than the one the logits put first."""
    decode = engine._decode
    engine._decode = lambda *a: (lambda o: (jnp.roll(o[0], 1, axis=-1),
                                            o[1]))(decode(*a))


def state_unchanged(engine):
    """A decode step returns the KV cache it was given."""
    decode = engine._decode
    engine._decode = lambda p, cache, t, pos: (decode(p, cache, t, pos)[0],
                                               cache)


def half_batch_left_out(engine):
    """The second half of the batch's rows get no logits."""
    decode = engine._decode

    def half(*a):
        logits, cache = decode(*a)
        return logits.at[logits.shape[0] // 2:].set(0.0), cache
    engine._decode = half


FAULTS = {f.__name__: f for f in (token_altered, state_unchanged,
                                  half_batch_left_out)}
