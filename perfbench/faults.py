"""Faults planted under the timed path, to show that the check catches them.

Each fault patches the program where the fault would arise and returns an
undo function. The benchmark's own runs never plant one: the tests in
``tests/`` and ``control.py`` do.
"""
from __future__ import annotations

from typing import Callable, Dict


def _patch(obj, name, value) -> Callable[[], None]:
    old = getattr(obj, name)
    setattr(obj, name, value)
    return lambda: setattr(obj, name, old)


# ------------------------------------------------------------------ solver

def solve_state_unchanged():
    """Every FISTA update returns the iterate it was given."""
    from repro.core import distributed, update_rules as ur

    def stuck(G, R, state, t, lam, *a, **kw):
        return ur.IterState(w_prev=state.w, w=state.w, j=state.j + 1)
    undo = [_patch(ur, "fista_update", stuck),
            _patch(distributed, "fista_update", stuck)]
    return lambda: [u() for u in undo]


def solve_half_batch():
    """Each sampled Gram uses half of its columns, averaged over those."""
    from repro.core import gram
    orig = gram.sampled_gram

    def half(X, y, idx, m_norm=None):
        return orig(X, y, idx[: idx.shape[0] // 2],
                    m_norm=None if m_norm is None else m_norm // 2)
    return _patch(gram, "sampled_gram", half)


def solve_answer_altered():
    """The returned iterate has one coordinate moved by 1% of its norm."""
    import jax.numpy as jnp
    from repro.core import sstep
    orig = sstep._extract

    def altered(rule, state):
        w = orig(rule, state)
        return w.at[0].add(1e-2 * jnp.linalg.norm(w))
    return _patch(sstep, "_extract", altered)


def solve_no_exchange():
    """The all-reduce of the sampled statistics is left out."""
    from repro.core import distributed

    class _Lax:
        def __getattr__(self, name):
            import jax
            return getattr(jax.lax, name)

        @staticmethod
        def psum(x, axes):
            return x

    class _Jax:
        lax = _Lax()

        def __getattr__(self, name):
            import jax
            return getattr(jax, name)
    return _patch(distributed, "jax", _Jax())


# ----------------------------------------------------------------- serving

def _wrap_serve_step(wrap):
    from repro.serve import decode
    orig = decode.make_serve_step

    def make(cfg, rules, **kw):
        return wrap(orig(cfg, rules, **kw), cfg)
    return _patch(decode, "make_serve_step", make)


def serve_token_altered():
    """Every fifth position, the step emits the token after the argmax."""
    import jax.numpy as jnp

    def wrap(step, cfg):
        def altered(params, cache, tokens, positions=None, page_table=None):
            nxt, logits, cache = step(params, cache, tokens, positions,
                                      page_table)
            bump = (positions % 5 == 0)[:, None]
            return jnp.where(bump, (nxt + 1) % cfg.vocab, nxt), logits, cache
        return altered
    return _wrap_serve_step(wrap)


def serve_state_unchanged():
    """The step returns the K/V cache it was given: nothing is written."""
    def wrap(step, cfg):
        def stuck(params, cache, tokens, positions=None, page_table=None):
            nxt, logits, _ = step(params, cache, tokens, positions,
                                  page_table)
            return nxt, logits, cache
        return stuck
    return _wrap_serve_step(wrap)


FAULTS: Dict[str, Dict[str, Callable]] = {
    "solve": dict(state_unchanged=solve_state_unchanged,
                  half_batch=solve_half_batch,
                  answer_altered=solve_answer_altered,
                  no_exchange=solve_no_exchange),
    "serve": dict(token_altered=serve_token_altered,
                  state_unchanged=serve_state_unchanged),
}
