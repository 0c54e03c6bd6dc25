"""StandardWorkflow: declarative model construction.

The Znicz ``StandardWorkflow`` builds the canonical training topology
from a ``layers`` config list (the reference MNIST/CIFAR/AlexNet sample
configs are exactly such lists). Re-provided here: each descriptor is
``{"type": <name>, ...params}``; the builder wires

    repeater -> loader -> forwards... -> evaluator -> decision
    decision -> gd[k] ... gd[0] -> repeater   (gd gated off non-TRAIN)
    end_point <- decision (gate: decision.complete)

and pairs every parameterized forward with its vjp-based GD unit. The
result runs eagerly (unit graph) or fused (veles_tpu.train), identically.

Three descriptor keys are the builder's own, whatever the type:
``learning_rate``/``weights_decay``/``momentum`` (that layer's GD
unit), ``remat`` (the fused step rematerializes the unit in its
backward pass, but for what the unit's code names as worth keeping:
``veles_tpu.remat``), and ``branch`` (the unit belongs to
the side branch of that name: it reads the main path where the branch
leaves it, or the branch's previous unit, and the main path goes on
past it. What a fused step makes of a branch is
``veles_tpu.train.step``'s; the eager graph runs it beside the main
path and no evaluator reads it).
"""

from veles_tpu.accelerated_units import AcceleratedWorkflow
from veles_tpu.nn.activation import ActivationUnit
from veles_tpu.nn.all2all import (All2All, All2AllRELU, All2AllSigmoid,
                                  All2AllSoftmax, All2AllStrictRELU,
                                  All2AllTanh)
from veles_tpu.nn.attention import (GroupedAttentionForward,
                                    LatentAttentionForward,
                                    MultiHeadAttentionForward)
from veles_tpu.nn.mlp import GatedMLPForward
from veles_tpu.nn.moe import MoEForward
from veles_tpu.nn.short_conv import ShortConvForward
from veles_tpu.nn.tokens import (TokenEmbeddingForward, TokenMergeForward,
                                 VocabularyHeadForward)
from veles_tpu.nn.conv import (Conv, ConvRELU, ConvSigmoid,
                               ConvStrictRELU, ConvTanh, Deconv)
from veles_tpu.nn.decision import DecisionGD, DecisionMSE
from veles_tpu.nn.dropout import DropoutBackward, DropoutForward
from veles_tpu.nn.evaluator import EvaluatorMSE, EvaluatorSoftmax
from veles_tpu.nn.gd import GradientDescentBase
from veles_tpu.nn.normalization import (LRNormalizerForward,
                                        RMSNormForward)
from veles_tpu.nn.pooling import (AvgPooling, Depooling, MaxAbsPooling,
                                  MaxPooling)
from veles_tpu.plumbing import Repeater

#: layer descriptor type -> forward unit class (Znicz MAPPING names)
LAYER_TYPES = {
    "all2all": All2All,
    "all2all_tanh": All2AllTanh,
    "all2all_relu": All2AllRELU,
    "all2all_str": All2AllStrictRELU,
    "all2all_sigmoid": All2AllSigmoid,
    "softmax": All2AllSoftmax,
    "conv": Conv,
    "conv_tanh": ConvTanh,
    "conv_relu": ConvRELU,
    "conv_str": ConvStrictRELU,
    "conv_sigmoid": ConvSigmoid,
    "deconv": Deconv,
    "max_pooling": MaxPooling,
    "maxabs_pooling": MaxAbsPooling,
    "avg_pooling": AvgPooling,
    "depooling": Depooling,
    "norm": LRNormalizerForward,
    "dropout": DropoutForward,
    "activation": ActivationUnit,
    "attention": MultiHeadAttentionForward,
    "moe": MoEForward,
    "token_embedding": TokenEmbeddingForward,
    "rms_norm": RMSNormForward,
    "latent_attention": LatentAttentionForward,
    "grouped_attention": GroupedAttentionForward,
    "gated_mlp": GatedMLPForward,
    "short_conv": ShortConvForward,
    "token_merge": TokenMergeForward,
    "vocabulary_head": VocabularyHeadForward,
}


class StandardWorkflow(AcceleratedWorkflow):
    """Canonical training workflow from a loader + layers config."""

    hide_from_registry = True

    def __init__(self, workflow=None, loader=None, layers=(),
                 loss="softmax", learning_rate=0.01, weights_decay=0.0,
                 momentum=0.0, lr_decay=1.0, solver="sgd",
                 max_epochs=None, fail_iterations=100,
                 mse_target_attr="minibatch_data", solver_hp=None,
                 **kwargs):
        super(StandardWorkflow, self).__init__(workflow, **kwargs)
        if loader is None:
            raise ValueError("StandardWorkflow needs a loader factory")

        self.repeater = Repeater(self)
        self.repeater.link_from(self.start_point)

        self.loader = loader(self) if callable(loader) else loader
        self.loader.link_from(self.repeater)

        # -- forward chain -------------------------------------------------
        self.forwards = []
        prev, prev_attr = self.loader, "minibatch_data"
        branch_ends, by_name = {}, {}
        for i, descr in enumerate(layers):
            descr = dict(descr)
            ltype = descr.pop("type")
            cls = LAYER_TYPES.get(ltype)
            if cls is None:
                raise ValueError("unknown layer type %r (have %s)" %
                                 (ltype, sorted(LAYER_TYPES)))
            lr = descr.pop("learning_rate", learning_rate)
            wd = descr.pop("weights_decay", weights_decay)
            mom = descr.pop("momentum", momentum)
            descr.setdefault("name", "%s%d" % (ltype, i))
            remat = descr.pop("remat", False)
            branch = descr.pop("branch", None)
            fwd = cls(self, **descr)
            fwd._gd_hyper = dict(learning_rate=lr, weights_decay=wd,
                                 momentum=mom)
            fwd.remat, fwd.branch = bool(remat), branch
            by_name[fwd.name] = fwd
            self.forwards.append(fwd)
            if hasattr(fwd, "link_context"):
                fwd.link_context(self.loader, by_name)
            if branch is not None:
                src, attr = branch_ends.get(branch, (prev, prev_attr))
                fwd.link_from(src)
                fwd.link_attrs(src, ("input", attr))
                branch_ends[branch] = fwd, "output"
                continue
            fwd.link_from(prev)
            fwd.link_attrs(prev, ("input", prev_attr))
            prev, prev_attr = fwd, "output"

        # -- evaluator + decision ------------------------------------------
        head = self.forwards[-1]
        if loss == "softmax":
            self.evaluator = EvaluatorSoftmax(self, name="evaluator")
            self.evaluator.link_attrs(self.loader,
                                      ("labels", "minibatch_labels"))
            self.decision = DecisionGD(self, max_epochs=max_epochs,
                                       fail_iterations=fail_iterations,
                                       name="decision")
            self.decision.link_attrs(self.evaluator,
                                     ("minibatch_n_err", "n_err"))
        elif loss == "mse":
            self.evaluator = EvaluatorMSE(self, name="evaluator")
            self.evaluator.link_attrs(self.loader,
                                      ("target", mse_target_attr))
            self.evaluator.link_attrs(self.loader,
                                      ("indices", "minibatch_indices"))
            self.decision = DecisionMSE(self, max_epochs=max_epochs,
                                        fail_iterations=fail_iterations,
                                        name="decision")
            self.decision.link_attrs(self.evaluator,
                                     ("minibatch_mse", "mse_per_sample"))
        else:
            raise ValueError("loss must be softmax or mse")
        self.evaluator.link_from(head)
        self.evaluator.link_attrs(head, "output")
        self.evaluator.link_attrs(self.loader,
                                  ("batch_size", "minibatch_size"))
        self.decision.link_from(self.evaluator)
        self.decision.link_attrs(self.loader, "minibatch_class",
                                 "last_minibatch", "epoch_ended",
                                 "epoch_number", "class_lengths",
                                 "minibatch_size")

        # -- backward chain ------------------------------------------------
        self.gds = []
        err_src, err_attr = self.evaluator, "err_output"
        for fwd in reversed(self.forwards):
            gd_cls = (DropoutBackward if isinstance(fwd, DropoutForward)
                      else GradientDescentBase)
            hyper = getattr(fwd, "_gd_hyper", {})
            gd = gd_cls(self, forward=fwd,
                        learning_rate=hyper.get("learning_rate",
                                                learning_rate),
                        weights_decay=hyper.get("weights_decay",
                                                weights_decay),
                        momentum=hyper.get("momentum", momentum),
                        solver=solver,
                        solver_hp=dict(
                            solver_hp or {},
                            **({"lr_decay": lr_decay}
                               if lr_decay != 1.0 else {})),
                        need_err_input=fwd is not self.forwards[0],
                        name="gd_" + fwd.name)
            gd.link_from(self.gds[-1] if self.gds else self.decision)
            gd.link_attrs(err_src, ("err_output", err_attr))
            gd.gate_skip = self.decision.gd_skip
            self.gds.append(gd)
            err_src, err_attr = gd, "err_input"

        self.repeater.link_from(self.gds[-1] if self.gds
                                else self.decision)
        self.repeater.gate_block = self.decision.complete
        self.end_point.link_from(self.decision)
        self.end_point.gate_block = ~self.decision.complete

    def set_testing(self, testing=True):
        """Inference mode: dropout off, no err_output generation, one
        forward-only epoch (then the decision stops the loop) — what
        ``--test`` and ensemble evaluation run."""
        self.evaluator.testing = testing
        self.decision.testing = testing
        if testing:
            # a snapshot-resumed workflow arrives with complete=True;
            # the test pass must re-open the loop for one epoch
            self.decision.complete.value = False
        for fwd in self.forwards:
            if isinstance(fwd, DropoutForward):
                fwd.testing = testing
