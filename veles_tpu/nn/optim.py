"""Parameter update rules (the reference GD units' "solvers": plain SGD
with momentum/weight-decay, AdaGrad, AdaDelta — ``manualrst_veles_
algorithms.rst`` Extras — plus Adam, which the 2015 reference predates).

All rules are pure functions over flat ``{name: array}`` dicts so they
jit into the fused train step unchanged:
``init(params) -> state``;
``update(params, grads, state, hp) -> (new_params, new_state)``.
"""

import jax.numpy as jnp


def _zeros_like(params):
    return {k: jnp.zeros_like(v) for k, v in params.items()}


def _lr_for(hp, key):
    """Per-parameter learning rate: Znicz GD exposes a separate
    ``learning_rate_bias``; generalized as hp['lr_overrides'][name]."""
    overrides = hp.get("lr_overrides")
    if overrides and key in overrides and overrides[key] is not None:
        return overrides[key]
    return hp["learning_rate"]


class Solver(object):
    name = None

    @staticmethod
    def init(params):
        raise NotImplementedError

    @staticmethod
    def update(params, grads, state, hp):
        raise NotImplementedError


class SGD(Solver):
    """lr * grad with classical momentum and L2 weight decay —
    the reference's default GradientDescent rule.

    ``hp['lr_decay']`` (optional, default 1.0) multiplies the learning
    rate by ``lr_decay**step`` — the classic exponential schedule; it
    rides a step counter in the solver state so the whole schedule jits
    into one compiled train segment (no per-epoch recompiles)."""

    name = "sgd"

    @staticmethod
    def init(params):
        return {"velocity": _zeros_like(params),
                "step": jnp.zeros((), jnp.float32)}

    @staticmethod
    def update(params, grads, state, hp):
        wd = hp.get("weight_decay", 0.0)
        mom = hp.get("momentum", 0.0)
        step = state.get("step", 0.0)
        scale = jnp.power(hp["lr_decay"], step) \
            if hp.get("lr_decay", 1.0) != 1.0 else 1.0
        new_p, new_v = {}, {}
        for k, p in params.items():
            g = grads[k] + wd * p
            v = mom * state["velocity"][k] - _lr_for(hp, k) * scale * g
            new_p[k] = p + v
            new_v[k] = v
        new_state = {"velocity": new_v}
        if "step" in state:
            # output structure must MIRROR the input's: a pre-r4
            # snapshot's state has no counter, and adding one here
            # would break the lax.scan carry pytree (such snapshots
            # predate lr_decay, so the schedule loses nothing)
            new_state["step"] = step + 1.0
        elif hp.get("lr_decay", 1.0) != 1.0:
            # runs at trace time (static dict structure), so once per
            # compile, not per step
            import logging
            logging.getLogger("SGD").warning(
                "lr_decay=%s configured but the restored solver state "
                "has no step counter (pre-r4 snapshot): the decay "
                "scale is pinned to 1.0", hp["lr_decay"])
        return new_p, new_state


class AdaGrad(Solver):
    name = "adagrad"

    @staticmethod
    def init(params):
        return {"accum": _zeros_like(params)}

    @staticmethod
    def update(params, grads, state, hp):
        wd = hp.get("weight_decay", 0.0)
        eps = hp.get("epsilon", 1e-8)
        new_p, new_a = {}, {}
        for k, p in params.items():
            g = grads[k] + wd * p
            a = state["accum"][k] + jnp.square(g)
            new_p[k] = p - _lr_for(hp, k) * g / (jnp.sqrt(a) + eps)
            new_a[k] = a
        return new_p, {"accum": new_a}


class AdaDelta(Solver):
    name = "adadelta"

    @staticmethod
    def init(params):
        return {"accum_g": _zeros_like(params),
                "accum_dx": _zeros_like(params)}

    @staticmethod
    def update(params, grads, state, hp):
        rho = hp.get("rho", 0.95)
        eps = hp.get("epsilon", 1e-6)
        wd = hp.get("weight_decay", 0.0)
        new_p, new_g, new_dx = {}, {}, {}
        for k, p in params.items():
            g = grads[k] + wd * p
            ag = rho * state["accum_g"][k] + (1 - rho) * jnp.square(g)
            dx = -jnp.sqrt(state["accum_dx"][k] + eps) / \
                jnp.sqrt(ag + eps) * g
            new_p[k] = p + dx
            new_g[k] = ag
            new_dx[k] = rho * state["accum_dx"][k] + \
                (1 - rho) * jnp.square(dx)
        return new_p, {"accum_g": new_g, "accum_dx": new_dx}


class Adam(Solver):
    """Adam with bias correction. ``hp['warmup_steps']`` (optional)
    scales the learning rate by ``min(1, t / warmup_steps)``, ``t`` the
    step being taken: at full rate Adam's first steps move every weight
    by the whole learning rate whatever its gradient."""

    name = "adam"

    @staticmethod
    def init(params):
        return {"m": _zeros_like(params), "v": _zeros_like(params),
                "t": jnp.zeros((), jnp.float32)}

    @staticmethod
    def update(params, grads, state, hp):
        b1 = hp.get("beta1", 0.9)
        b2 = hp.get("beta2", 0.999)
        eps = hp.get("epsilon", 1e-8)
        wd = hp.get("weight_decay", 0.0)
        t = state["t"] + 1.0
        correction = jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        if hp.get("warmup_steps"):
            correction = correction * jnp.minimum(
                1.0, t / hp["warmup_steps"])
        new_p, new_m, new_v = {}, {}, {}
        for k, p in params.items():
            g = grads[k] + wd * p
            m = b1 * state["m"][k] + (1 - b1) * g
            v = b2 * state["v"][k] + (1 - b2) * jnp.square(g)
            new_p[k] = p - _lr_for(hp, k) * correction * m / \
                (jnp.sqrt(v) + eps)
            new_m[k] = m
            new_v[k] = v
        return new_p, {"m": new_m, "v": new_v, "t": t}


SOLVERS = {cls.name: cls for cls in (SGD, AdaGrad, AdaDelta, Adam)}


def get_solver(name):
    if isinstance(name, type) and issubclass(name, Solver):
        return name
    try:
        return SOLVERS[name]
    except KeyError:
        raise ValueError("unknown solver %r (have %s)" %
                         (name, sorted(SOLVERS)))
