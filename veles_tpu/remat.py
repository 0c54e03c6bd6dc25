"""What a rematerialized unit keeps across its recomputation.

The fused step re-runs a unit's forward in the backward pass where the
unit's descriptor says ``remat`` (``train/step.py``
``_apply_in_context``). Most of what a unit computes is cheap to make
again for the bytes it would hold; a few values are not (the attention
core's output and row statistics: a Mosaic forward kernel of 3.2 ms
for 85 MB, PR 30). The mechanism has two halves and both live here:

* the code that makes such a value passes it through :func:`keep`
  where it enters a ``custom_vjp``'s residuals;
* the step wraps a ``remat`` unit in :func:`checkpoint`, whose one
  policy saves what was kept and nothing else, and which says how
  many bytes that was (the gauge ``veles_remat_kept_bytes{unit}``).

A unit that keeps nothing traces to the program a plain
``jax.checkpoint`` gives it. Outside a checkpoint (no ``remat``, a
forward-only program, eager ``Unit.run``) :func:`keep` is an identity.
"""

import contextvars

import jax
from jax.ad_checkpoint import checkpoint_name

#: the one name :func:`keep` gives and the policy saves
KEPT = "veles_kept"

#: the sizes kept so far by the :func:`checkpoint` call being traced
_tally = contextvars.ContextVar("veles_remat_tally", default=None)


def keep(*values):
    """``values``, each named as worth keeping across the
    rematerialization of the unit that is being traced."""
    tally = _tally.get()
    if tally is not None:
        tally.extend(v.size * v.dtype.itemsize for v in values)
    return tuple(checkpoint_name(v, KEPT) for v in values)


def checkpoint(fn):
    """``fn`` rematerialized in the backward pass but for what it
    passes through :func:`keep`: ``call(*args) -> (fn(*args), bytes
    kept)``, the bytes counted while the call is traced under a
    gradient (0 where nothing differentiates it). ``fn`` is to be a
    function object of the caller's own: JAX caches a function's trace
    by identity, and a cached trace passes through :func:`keep` no
    second time."""
    inner = jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(KEPT))

    def call(*args):
        tally = []
        token = _tally.set(tally)
        try:
            out = inner(*args)
        finally:
            _tally.reset(token)
        return out, sum(tally)
    return call
