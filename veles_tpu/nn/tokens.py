"""Units at the two ends of a token model: the embedding of ids, the
head over the vocabulary with its per-token loss, and the merge that
opens a side branch which predicts a later token.

A token data set's sample is a row of ids, ``positions`` of them to
embed and a few more of lookahead, so that every position has a
target for the main head (the next id) and for each side branch (the
id ``shift`` further on). The vocabulary may be a slice of a larger
model's: ids, logits and losses are then over the slice.
"""

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.memory import Array
from veles_tpu.nn.base import ForwardBase, NamedParamsForward
from veles_tpu.nn.normalization import rms_norm
from veles_tpu.nn.precision import get_policy
from veles_tpu.telemetry.registry import get_registry


class TokenEmbeddingForward(ForwardBase):
    """ids (batch, positions + lookahead) -> (batch, positions, dim):
    row ``id`` of ``weights`` (vocabulary, dim) for each of the first
    ``positions`` ids."""

    def __init__(self, workflow, vocabulary=None, dim=None,
                 positions=None, **kwargs):
        kwargs.setdefault("include_bias", False)
        super(TokenEmbeddingForward, self).__init__(workflow, **kwargs)
        self.vocabulary, self.dim = int(vocabulary), int(dim)
        self.positions = int(positions)

    def weights_shape_for(self, input_shape):
        return (self.vocabulary, self.dim)

    def output_shape_for(self, input_shape):
        return (input_shape[0], self.positions, self.dim)

    def fill_weights(self):
        # a table has no fan-in: the default is unit variance over dim
        if not self.weights_stddev:
            self.weights_stddev = 1.0 / numpy.sqrt(self.dim)
        super(TokenEmbeddingForward, self).fill_weights()

    def apply(self, params, x):
        return embed(params["weights"], x[:, :self.positions])


def embed(table, ids):
    return get_policy().cast_out(
        jnp.take(table, ids.astype(jnp.int32), axis=0))


class TokenMergeForward(NamedParamsForward):
    """Opens a side branch that predicts the id ``shift`` beyond the
    main head's target (multi-token prediction, DeepSeek-V3's module):
    ``[rms_norm(Emb(id_{t+shift})); rms_norm(h_t)] W``, the embedding's
    half first, ``Emb`` the table of the unit named ``embedding``,
    ``h`` the main path's state where the branch leaves it.

    What it reads beyond ``x`` the fused step hands it in the step's
    context (``apply_step``); in the eager graph ``tokens`` and
    ``table`` are linked attributes (``link_context``).
    ``objective_weight`` is the weight of the branch's loss in the
    objective; the trainer reads it and ``shift`` off the unit."""

    hide_from_registry = False
    PARAMS = ("token_norm", "state_norm", "weights")

    def __init__(self, workflow, embedding=None, shift=1,
                 objective_weight=1.0, eps=1e-5, **kwargs):
        super(TokenMergeForward, self).__init__(workflow, **kwargs)
        self.embedding = embedding
        self.shift = int(shift)
        self.objective_weight = float(objective_weight)
        self.eps = float(eps)
        self.tokens = None
        self.table = None

    def param_shapes(self, input_shape):
        dim = input_shape[-1]
        return {"token_norm": ((dim,), "gain"),
                "state_norm": ((dim,), "gain"),
                "weights": ((2 * dim, dim), "matrix")}

    def link_context(self, loader, units):
        self.link_attrs(loader, ("tokens", "minibatch_data"))
        self.link_attrs(units[self.embedding], ("table", "weights"))

    def merge(self, params, x, tokens, table):
        pol = get_policy()
        seq = x.shape[1]
        emb = embed(table, tokens[:, self.shift:self.shift + seq])
        both = jnp.concatenate(
            [rms_norm(emb, params["token_norm"], self.eps),
             rms_norm(x, params["state_norm"], self.eps)], -1)
        both, w = pol.cast_in(both, params["weights"])
        return pol.cast_out(jnp.dot(
            both, w, preferred_element_type=pol.accum_dtype))

    def apply_step(self, params, x, ctx):
        return self.merge(params, x, ctx.tokens,
                          ctx.params_of(self.embedding)["weights"]), {}

    def apply(self, params, x):
        def mem(a):
            return a.devmem if isinstance(a, Array) else a
        return self.merge(params, x, mem(self.tokens), mem(self.table))


class VocabularyHeadForward(ForwardBase):
    """The head over the vocabulary: ``x weights`` (dim, vocabulary),
    no bias. ``apply`` gives probabilities and ``apply_for_grad``
    logits, as the softmax head does; a fused step asks for
    :meth:`token_losses` instead, which never holds the logits of more
    than ``chunk`` tokens.

    ``tied_to=<unit name>``: the head has no weights of its own and
    reads the table of the embedding of that name, ``x table^T``. A
    fused step hands it the table as it differentiates it
    (:meth:`step_params`), so the table's gradient is the sum of its
    two readers'; in the eager graph ``table`` is a linked attribute
    (``link_context``). A snapshot holds the table once."""

    #: a head pickled before the key existed owns its weights
    tied_to = None

    def __init__(self, workflow, vocabulary=None, chunk=2048,
                 tied_to=None, **kwargs):
        kwargs.setdefault("include_bias", False)
        super(VocabularyHeadForward, self).__init__(workflow, **kwargs)
        self.vocabulary = int(vocabulary)
        self.chunk = int(chunk)
        self.tied_to = tied_to
        self.table = None

    @property
    def has_weights(self):
        return not self.tied_to

    def weights_shape_for(self, input_shape):
        return (input_shape[-1], self.vocabulary)

    def output_shape_for(self, input_shape):
        return tuple(input_shape[:-1]) + (self.vocabulary,)

    def link_context(self, loader, units):
        if self.tied_to:
            self.link_attrs(units[self.tied_to], ("table", "weights"))

    def step_params(self, params, ctx):
        """What :meth:`token_losses` and :meth:`apply_for_grad` take
        as ``params`` in a fused step: the head's own, or under
        ``tied_to`` the embedding's table out of the step's context."""
        if not self.tied_to:
            return params
        return {"table": ctx.params_of(self.tied_to)["weights"]}

    def apply_for_grad(self, params, x):
        if not self.tied_to:
            x, w = get_policy().cast_in(x, params["weights"])
            return jnp.dot(x, w, preferred_element_type=jnp.float32)
        table = params.get("table")
        if table is None:  # the eager graph: the linked attribute
            table = self.table.devmem if isinstance(self.table, Array) \
                else self.table
        x, table = get_policy().cast_in(x, table)
        return jax.lax.dot_general(
            x, table, (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def apply(self, params, x):
        return jax.nn.softmax(self.apply_for_grad(params, x), axis=-1)

    def jax_run(self):
        if not self.tied_to:
            return super(VocabularyHeadForward, self).jax_run()
        # the table as an argument: closed over, the first trace's
        # would be served for ever
        self.unmap_vectors(self.input, self.table)
        self.output.assign_devmem(self.jit(self.apply)(
            {"table": self.table.devmem}, self._input_devmem()))

    def token_losses(self, params, x, targets, loss_scope):
        """``(loss, wrong)`` of every token, float32 and bool, shaped
        as ``targets``: the cross-entropy of the softmax over the
        vocabulary against ``targets`` and whether the largest logit
        missed it. Tokens go ``chunk`` at a time through a
        rematerialized map, forward and backward, so that one chunk's
        logits are all that is ever held. The softmax and the loss run
        under ``loss_scope()``. Traced, sets
        ``veles_head_tied{unit}``."""
        get_registry().gauge(
            "veles_head_tied", "1 where the head reads the embedding's "
            "table and has no weights of its own", labels=("unit",)
        ).labels(unit=self.name).set(float(bool(self.tied_to)))
        flat = x.reshape(-1, x.shape[-1])
        n_chunks = max(1, flat.shape[0] // self.chunk)
        if flat.shape[0] % n_chunks:
            n_chunks = 1

        @jax.checkpoint
        def one(args):
            h, t = args
            logits = self.apply_for_grad(params, h)
            with loss_scope():
                logp = jax.nn.log_softmax(logits)
                loss = -jnp.take_along_axis(logp, t[:, None], 1)[:, 0]
                return loss, jnp.argmax(logits, axis=1) != t

        loss, wrong = jax.lax.map(one, (
            flat.reshape(n_chunks, -1, flat.shape[-1]),
            targets.reshape(n_chunks, -1).astype(jnp.int32)))
        return loss.reshape(targets.shape), wrong.reshape(targets.shape)
