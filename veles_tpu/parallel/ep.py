"""Expert parallelism: mixture-of-experts FFN over an ``expert`` axis.

The classic Switch/GShard schedule, TPU-native: tokens are sharded
over the mesh's ``expert`` axis (each device owns one shard of tokens
AND one expert's FFN weights); a top-1 router picks an expert per
token; tokens travel to their expert's device and back via
``lax.all_to_all`` over ICI; static shapes throughout (fixed per-expert
capacity, overflow dropped — the standard Switch contract, which is
what keeps the whole thing one compiled SPMD program).

The 2015 reference predates MoE entirely; this is a first-class
capability of the dp/tp/pp/sp/ep sharding family, designed per the
task brief rather than ported.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map


def moe_ffn(x, router_w, w_up, w_down, mesh, axis="expert",
            capacity_factor=1.25, activation=jax.nn.relu):
    """Top-1 mixture-of-experts FFN.

    * ``x`` — (tokens, d), sharded over ``axis`` on dim 0 (or
      replicated: the shard_map in_spec shards it);
    * ``router_w`` — (d, n_experts), replicated;
    * ``w_up`` — (n_experts, d, hidden), sharded over ``axis`` dim 0;
    * ``w_down`` — (n_experts, hidden, d), sharded over ``axis`` dim 0.

    Returns (tokens, d): each token's chosen expert's
    ``down(act(up(x)))`` scaled by its router probability — zero for
    tokens dropped by the capacity limit (Switch semantics).
    Differentiable in everything, router included (the probability
    scale carries the gradient).
    """
    n_experts = mesh.shape[axis]
    if router_w.shape[1] != n_experts:
        raise ValueError("router has %d experts, mesh axis %r is %d" %
                         (router_w.shape[1], axis, n_experts))

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis), P(), P(axis), P(axis)),
        out_specs=P(axis), check_vma=False)
    def run(xs, rw, up, down):
        t, d = xs.shape                      # local token shard
        up, down = up[0], down[0]            # this device's expert
        capacity = max(1, int(-(-t * capacity_factor // n_experts)))
        logits = xs @ rw                     # (t, E)
        probs = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(probs, axis=-1)            # (t,)
        gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
        # position of each token within its expert's capacity window
        onehot = jax.nn.one_hot(expert, n_experts)     # (t, E)
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # (t, E)
        pos = jnp.sum(pos, axis=-1).astype(jnp.int32)  # (t,)
        keep = pos < capacity
        # dispatch buffer: (E, C, d) — slot [e, c] holds the token this
        # shard routes to expert e at capacity slot c (zeros elsewhere)
        slot = jnp.where(keep, expert * capacity + pos, -1)
        dispatch = jnp.zeros((n_experts * capacity, d), xs.dtype)
        dispatch = dispatch.at[jnp.maximum(slot, 0)].add(
            xs * keep[:, None].astype(xs.dtype))
        dispatch = dispatch.reshape(n_experts, capacity, d)
        # all_to_all: dim0 switches meaning source-shard <-> expert;
        # after it, THIS device holds every shard's tokens for ITS
        # expert: (n_shards, C, d)
        inbound = jax.lax.all_to_all(dispatch, axis, 0, 0, tiled=False)
        h = activation(jnp.einsum(
            "scd,dh->sch", inbound, up,
            preferred_element_type=jnp.float32).astype(xs.dtype))
        out = jnp.einsum("sch,hd->scd", h, down,
                         preferred_element_type=jnp.float32).astype(
            xs.dtype)
        # route results back to their source shards
        outbound = jax.lax.all_to_all(out, axis, 0, 0, tiled=False)
        flat = outbound.reshape(n_experts * capacity, d)
        gathered = flat[jnp.maximum(slot, 0)]
        return gathered * (gate * keep)[:, None].astype(xs.dtype)

    return run(x, router_w, w_up, w_down)


def moe_ffn_reference(x, router_w, w_up, w_down, n_experts,
                      capacity_factor=1.25, activation=jax.nn.relu,
                      n_shards=None):
    """Dense single-device reference with IDENTICAL semantics
    (per-shard capacity, same drop order) for parity tests."""
    n_shards = n_experts if n_shards is None else n_shards
    t_total, d = x.shape
    if t_total % n_shards:
        # the sharded path would reject this too (shard_map needs the
        # token dim divisible); a silent zero-tail here would be a
        # wrong "reference"
        raise ValueError("%d tokens not divisible by %d shards" %
                         (t_total, n_shards))
    t = t_total // n_shards
    out = jnp.zeros_like(x)
    for s in range(n_shards):
        xs = x[s * t:(s + 1) * t]
        capacity = max(1, int(-(-t * capacity_factor // n_experts)))
        probs = jax.nn.softmax(xs @ router_w, axis=-1)
        expert = jnp.argmax(probs, axis=-1)
        gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
        onehot = jax.nn.one_hot(expert, n_experts)
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1.0) * onehot,
                      axis=-1).astype(jnp.int32)
        keep = pos < capacity
        h = activation(jnp.einsum("td,edh->teh", xs, w_up,
                                  preferred_element_type=jnp.float32)
                       .astype(x.dtype))
        y = jnp.einsum("teh,ehd->ted", h, w_down,
                       preferred_element_type=jnp.float32).astype(
            x.dtype)
        picked = y[jnp.arange(t), expert]
        out = out.at[s * t:(s + 1) * t].set(
            picked * (gate * keep)[:, None].astype(x.dtype))
    return out


def load_balance_loss(probs, weights=None):
    """Switch-style load-balancing auxiliary loss.

    ``probs`` — (tokens, E) router softmax. With ``f_e`` the fraction
    of tokens whose top-1 choice is expert e and ``P_e`` the mean
    router probability of e, returns ``E * sum_e f_e * P_e`` —
    minimized (=1) at uniform routing; the gradient flows through
    ``P`` (``f`` is piecewise constant), nudging the router away from
    collapse onto a few experts (observed here: a 1-epoch run
    concentrating 96 tokens onto 2 of 4 experts).

    ``weights`` (tokens,) optionally masks/weights tokens — the fused
    trainer passes the padded-row validity mask so a short tail batch
    (whose padding rows are all-zero and would all tie onto expert 0)
    cannot distort the balance statistics.
    """
    n_experts = probs.shape[-1]
    assignment = jax.nn.one_hot(jnp.argmax(probs, axis=-1), n_experts)
    if weights is None:
        f = jnp.mean(assignment, axis=0)
        p = jnp.mean(probs, axis=0)
    else:
        w = weights.astype(probs.dtype)
        w = w / jnp.maximum(jnp.sum(w), 1.0)
        f = jnp.sum(assignment * w[:, None], axis=0)
        p = jnp.sum(probs * w[:, None], axis=0)
    return n_experts * jnp.sum(f * p)
